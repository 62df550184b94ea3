"""Attention kernels: Pallas flash attention + XLA reference.

The reference has no attention anywhere (SURVEY §2.6) — sequence length
only sizes its token batch.  A complete framework needs the full model, and
long-context support is first-class here: this module provides the
single-chip blockwise (flash) attention kernel whose online-softmax
accumulator is also the building block of the ring attention in
:mod:`flashmoe_tpu.parallel.ringattn` (same math, kv blocks arriving over
ICI instead of from HBM).

Layouts: q/k/v are [B, N, T, D] (batch, heads, time, head_dim); the
training call (:func:`flash_attention`) has GQA handled by the caller
repeating kv heads (cheap view under XLA), the serving call
(:func:`flash_span_attention`: a span of queries that starts anywhere in
its context) reads a K/V head's blocks from every query head of its group.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashmoe_tpu.config import LANE, STATE_MIXERS, WINDOW_MIXER
from flashmoe_tpu.ops.conv import conv_attention
from flashmoe_tpu.ops.expert import _VMEM_DEFAULT, _vmem_params
from flashmoe_tpu.ops.kda import kda_attention
from flashmoe_tpu.ops.ssm import ssm_attention
from flashmoe_tpu.utils.telemetry import trace_span

NEG_INF = -1e30


def attention_xla(q, k, v, *, causal: bool = True, q_offset: int | jax.Array = 0,
                  kv_offset: int | jax.Array = 0, scale: float | None = None):
    """Plain XLA attention (oracle). q: [B, N, Tq, D], k/v: [B, N, Tk, D].

    ``q_offset``/``kv_offset`` are the global positions of the first row /
    column — needed when the caller holds sequence shards (ring/SP)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum(
        "bntd,bnsd->bnts", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        qi = jnp.arange(tq)[:, None] + q_offset
        ki = jnp.arange(tk)[None, :] + kv_offset
        logits = jnp.where((qi >= ki)[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bnts,bnsd->bntd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


# ----------------------------------------------------------------------
# Multi-head latent attention (MLA: DeepSeek-V2/V3 family), plain XLA
# ----------------------------------------------------------------------
#
# x is the normed input of a block, h indexes the heads:
#   c_q = RMSNorm(x W_qa);  [q_nope_h | q_rope_h] = c_q W_qb;  RoPE(q_rope_h)
#   [c | k_r] = x W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)  (one key
#   for all heads);  [k_nope_h | v_h] = c_kv W_kvb
#   score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_rope(s))
#                   / sqrt(d_nope + d_rope),  causal softmax,  o_h = sum p v_h
# With ``cfg.mla_rank_scale`` the two normed latents carry a factor each:
# c_q is sqrt(hidden / q_lora_rank) RMSNorm(.), so both parts of every
# head's query; c_kv is sqrt(hidden / kv_lora_rank) RMSNorm(.), so k_nope
# and v and NOT the shared rotary key.  The factor is part of the norm's
# weight, so every form below (and the cached row) has it and none names it.
# What a cache keeps of a token is the LATENT row [c_kv | k_rope]
# (kv_lora_rank + qk_rope_head_dim elements, nothing per head).  The
# ABSORBED form gives the same numbers in another order and never
# decompresses the context: with W_kvb split per head into W_uk_h and
# W_uv_h,  q~_h = W_uk_h q_nope_h,  score_h = [q~_h | q_rope_h] . latent(s),
# o~_h = sum p latent(s),  o_h = o~_h[:rank] W_uv_h.

def rms_norm(x, w, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def rope_adjacent(x, positions, theta):
    """Rotary embedding over ADJACENT pairs (2i, 2i+1) of the last axis
    (the published ``rope_interleave``).  x: [B, T, D] or [B, T, N, D];
    positions: [B, T]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq     # [B, T, half]
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_project(layer, x, cfg, positions):
    """x: [B, T, H] normed -> (q_nope [B, T, N, d_nope], q_rope
    [B, T, N, d_rope] roped, latent [B, T, rank + d_rope]: the normed
    ``c_kv`` beside the roped shared key, the row a cache keeps; with
    ``cfg.mla_rank_scale`` the normed ``c_q`` and ``c_kv`` carry their
    factors)."""
    b, t, _ = x.shape
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc = cfg.kv_lora_rank
    q_norm, kv_norm = layer.get("q_a_norm"), layer["kv_a_norm"]
    if cfg.mla_rank_scale:
        h = cfg.hidden_size
        kv_norm = kv_norm.astype(jnp.float32) * (h / dc) ** 0.5
        if cfg.q_lora_rank:
            q_norm = q_norm.astype(jnp.float32) * (
                h / cfg.q_lora_rank) ** 0.5
    if cfg.q_lora_rank:
        c_q = rms_norm(x @ layer["wq_a"].astype(x.dtype), q_norm,
                       cfg.norm_eps)
        q = c_q @ layer["wq_b"].astype(x.dtype)
    else:                       # the published null rank: queries direct
        q = x @ layer["wq"].astype(x.dtype)
    q = q.reshape(b, t, nh, dn + dr)
    q_rope = rope_adjacent(q[..., dn:], positions, cfg.rope_theta)
    kv = x @ layer["wkv_a"].astype(x.dtype)                   # [B, T, dc+dr]
    c_kv = rms_norm(kv[..., :dc], kv_norm, cfg.norm_eps)
    k_rope = rope_adjacent(kv[..., dc:], positions, cfg.rope_theta)
    return q[..., :dn], q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def _mla_up_weights(layer, cfg, dt):
    """``wkv_b`` split per head: (W_uk [rank, N, d_nope], W_uv [rank, N,
    d_v])."""
    dn = cfg.qk_nope_head_dim
    w_kvb = layer["wkv_b"].astype(dt).reshape(
        cfg.kv_lora_rank, cfg.num_heads, dn + cfg.v_head_dim)
    return w_kvb[..., :dn], w_kvb[..., dn:]


def _absorbed_query(q_nope, q_rope, w_uk):
    """The absorbed form's query: ``[W_uk q_nope | q_rope]``, one row of
    rank + d_rope elements a head, scored against latent rows as they
    are cached.  [B, T, N, rank + d_rope]."""
    q_lat = jnp.einsum("btnd,cnd->btnc", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_nope.dtype), q_rope], axis=-1)


def mla_attend(layer, q_nope, q_rope, latent_ctx, cfg, q_pos, *,
               absorbed: bool):
    """Causal MLA of T queries a row over a context of latent rows.

    q_nope / q_rope: [B, T, N, .]; latent_ctx: [B, S, rank + d_rope], row
    s the latent of position s; q_pos: [B, T] the queries' positions,
    consecutive along T (a query sees s <= its position: rows past it may
    hold anything).  ``absorbed`` picks the order of the products (see
    above): the plain form decompresses K and V of all S rows, the
    absorbed form touches the latent rows only.  The plain form's scores
    and softmax run blockwise through :func:`flash_span_attention` where
    :func:`span_attention_arm` says so (a long span on a TPU), as float32
    logits ``[B, N, T, S]`` in plain XLA elsewhere: the form the kernel
    is held against.  Returns the block's attention output [B, T, H]."""
    b, t, nh, dn = q_nope.shape
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
    dt = q_nope.dtype
    w_uk, w_uv = _mla_up_weights(layer, cfg, dt)
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    f32 = dict(preferred_element_type=jnp.float32)
    if not absorbed and span_attention_arm(
            t, latent_ctx.shape[1], nh, (dn, cfg.qk_rope_head_dim), dv,
            dt) == "flash":
        with trace_span("attn.mla_prefill"):
            c_kv, k_rope = latent_ctx[..., :dc], latent_ctx[..., dc:]
            k_nope = jnp.einsum("bsc,cnd->bnsd", c_kv, w_uk, **f32)
            v = jnp.einsum("bsc,cnd->bnsd", c_kv, w_uv, **f32)
            # the rotary key is ONE [S, d_rope] array for all heads
            ctx = _flash_span_ctx(
                (q_nope, q_rope), (k_nope.astype(dt), k_rope[:, None]),
                v.astype(dt), q_pos, scale)
        return ctx @ layer["wo"].astype(dt)
    mask = (jnp.arange(latent_ctx.shape[1])[None, None, None, :]
            <= q_pos[:, None, :, None])
    if absorbed:
        with trace_span("attn.mla_decode"):
            q_cat = _absorbed_query(q_nope, q_rope, w_uk)
            logits = jnp.einsum("btnc,bsc->bnts", q_cat, latent_ctx,
                                **f32) * scale
            probs = jax.nn.softmax(jnp.where(mask, logits, NEG_INF),
                                   axis=-1).astype(dt)
            # heads and span rows as ONE row axis of a plain batched
            # product over the latent rows
            o_lat = jnp.einsum(
                "bms,bsc->bmc", probs.reshape(b, nh * t, -1), latent_ctx,
                **f32).reshape(b, nh, t, -1).transpose(0, 2, 1, 3)[..., :dc]
            ctx = jnp.einsum("btnc,cnd->btnd", o_lat.astype(dt), w_uv,
                             **f32)
    else:
        with trace_span("attn.mla_prefill"):
            c_kv, k_rope = latent_ctx[..., :dc], latent_ctx[..., dc:]
            k_nope = jnp.einsum("bsc,cnd->bsnd", c_kv, w_uk,
                                **f32).astype(dt)
            v = jnp.einsum("bsc,cnd->bsnd", c_kv, w_uv, **f32).astype(dt)
            logits = (jnp.einsum("btnd,bsnd->bnts", q_nope, k_nope, **f32)
                      + jnp.einsum("btnr,bsr->bnts", q_rope, k_rope,
                                   **f32)) * scale
            probs = jax.nn.softmax(jnp.where(mask, logits, NEG_INF),
                                   axis=-1).astype(dt)
            ctx = jnp.einsum("bnts,bsnd->btnd", probs, v, **f32)
    ctx = ctx.reshape(b, t, nh * dv).astype(dt)
    return ctx @ layer["wo"].astype(dt)


def store_latent(pool, li: int, rows_kv, page_ids, rows):
    """Scatter a span's latent rows into layer ``li`` of the latent pool.
    pool: [L, P, page, R] (``serving/kvcache.LatentPagedCache``: a row is
    the latent padded with zeros to whole lanes); rows_kv: [B, T, C];
    page_ids / rows: [B, T].  One window of R elements a token.  ``rows``
    None: the span fills WHOLE pages (a prefill chunk); page_ids is then
    [B, T // page], one id a page, and a page is one window (the chip
    walks a scatter window by window: 1024 token windows a layer were a
    fifth of a chunk's time).  The layer is an index of the scatter, not
    a slice taken out and put back: that copied the layer's 300 MB twice
    in every program."""
    page, r = pool.shape[2:]
    upd = jnp.pad(rows_kv, ((0, 0), (0, 0), (0, r - rows_kv.shape[-1])))
    lcol = jnp.full(page_ids.shape, li, page_ids.dtype)
    if rows is None:
        idx = jnp.stack([lcol, page_ids], axis=-1).reshape(-1, 2)
        upd = upd.reshape(idx.shape[0], page, r)
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1))
    else:
        idx = jnp.stack([lcol, page_ids, rows], axis=-1).reshape(-1, 3)
        upd = upd.reshape(-1, r)
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2))
    return jax.lax.scatter(
        pool, idx, upd.astype(pool.dtype), dnums,
        mode=jax.lax.GatherScatterMode.CLIP)


def gather_latent(pool, li: int, block_tables, c: int):
    """Each slot's context window from layer ``li`` of the latent pool.
    pool: [L, P, page, R]; block_tables: [B, n] -> [B, n * page, C], the
    rows without their padding; rows past a slot's length are scratch and
    are masked by the caller."""
    ctx = pool[li, block_tables]                       # [B, n, page, R]
    return ctx.reshape(ctx.shape[0], -1, ctx.shape[-1])[..., :c]


def mla_paged_attention(layer, x, cfg, pool, li, pos, write, block_tables,
                        *, absorbed: bool):
    """THE multi-head latent attention of every cached path: project a
    span of T tokens a slot, write its latent rows to layer ``li``'s
    pages, attend over the context.  Prefill (whole and chunked), decode
    and verify all call this and nothing else.  Two arms, by
    :func:`kv_attention_arm` (the rule the K/V layers ask): a short span
    in the absorbed form over a paged pool on a TPU reads each slot's own
    pages in place (:func:`paged_decode_attention` over ONE pool: a
    latent row is the key of every head and its first ``kv_lora_rank``
    columns are the value); everything else stores, gathers the context
    and attends through :func:`mla_attend` (:func:`store_latent`,
    :func:`gather_latent`; in plain XLA, the form the kernel is held
    against, or for a long span on a TPU blockwise:
    :func:`span_attention_arm`).

    x: [B, T, H] normed; pool: the latent pool [L, P, page, R], or None
    for a whole prompt at once (nothing is cached yet, the context is the
    span itself); pos: [B, T] absolute positions, consecutive along T;
    write: ``(page_ids, rows)``, each [B, T], where the span's rows go (or
    ``(page_ids [B, T // page], None)`` for a span of whole pages);
    block_tables: [B, n] the slots' pages.  Returns (attention
    output [B, T, H], the pool, the span's latent rows [B, T, C])."""
    q_nope, q_rope, latent = mla_project(layer, x, cfg, pos)
    if pool is None:
        return (mla_attend(layer, q_nope, q_rope, latent, cfg, pos,
                           absorbed=absorbed), pool, latent)
    b, t, nh, _ = q_nope.shape
    page, r = pool.shape[2:]
    if (absorbed and write[1] is not None and kv_attention_arm(
            t, page, 1, r, pool.dtype, pools=1) == "paged_kernel"):
        dt, dc = q_nope.dtype, cfg.kv_lora_rank
        with trace_span("attn.mla_decode"):
            w_uk, w_uv = _mla_up_weights(layer, cfg, dt)
            lanes = lambda a: jnp.pad(
                a, [(0, 0)] * (a.ndim - 1) + [(0, r - a.shape[-1])])
            # every head of a slot against the slot's ONE row a token
            o_lat, (pool,) = paged_decode_attention(
                lanes(_absorbed_query(q_nope, q_rope, w_uk)),
                (lanes(latent)[:, :, None],), (pool[:, :, None],), li,
                block_tables, pos[:, 0], write, v_width=dc,
                scale=(q_nope.shape[-1] + cfg.qk_rope_head_dim) ** -0.5,
                interpret=jax.default_backend() != "tpu")
            ctx = jnp.einsum("btnc,cnd->btnd", o_lat.reshape(b, t, nh, dc),
                             w_uv, preferred_element_type=jnp.float32)
        ctx = ctx.reshape(b, t, -1).astype(dt)
        return ctx @ layer["wo"].astype(dt), pool[:, :, 0], latent
    pool = store_latent(pool, li, latent, *write)
    ctx = gather_latent(pool, li, block_tables, latent.shape[-1])
    return (mla_attend(layer, q_nope, q_rope, ctx, cfg, pos,
                       absorbed=absorbed), pool, latent)


# ----------------------------------------------------------------------
# K/V attention over a paged cache (MHA or GQA, RoPE over halves), plain XLA
# ----------------------------------------------------------------------
#
# The twin of the MLA trio above for ``attention_kind == 'mha'``: a cache
# keeps of a token its roped K and its V per kv head, in a pair of pools
# ``[L, P, N_kv, page, D]`` (``serving/kvcache.PagedKVCache``; generate's
# dense ``KVCache`` is the same layout with one ``T_max``-row page a batch
# row).  The functions of this section are the plain form; a short span
# over a paged pool on a TPU takes the kernel further down instead
# (``kv_attention_arm``), and a long span's scores run through the flash
# kernel at the end of the file (``span_attention_arm``).

def rope_halves(q, k, positions, theta):
    """Rotary position embeddings over the two HALVES (i, i + D/2) of the
    last axis.  q/k: [B, T, N, D]; positions: [B, T]."""
    d = q.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate(
            [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
        ).astype(x.dtype)

    return rot(q), rot(k)


def kv_project(layer, x, cfg, positions, mixer: str = "mha"):
    """x: [B, T, H] normed -> (q [B, T, N, D], k and v [B, T, N_kv, D]),
    q and k roped at ``positions`` [B, T] (unless ``cfg.use_rope`` is
    off); under ``cfg.qk_norm`` every head of q and of k goes through an
    RMSNorm over its width first.  ``mixer``: the layer's kind.  THE rule
    of which layers rotate: in a model with window layers ("swa") they do
    and its "mha" layers, which see every key, do not; a model without
    rotates every layer."""
    b, t, _ = x.shape
    nh, nkv, dh = (cfg.num_heads, cfg.resolved_num_kv_heads,
                   cfg.resolved_head_dim)
    q = (x @ layer["wq"].astype(x.dtype)).reshape(b, t, nh, dh)
    k = (x @ layer["wk"].astype(x.dtype)).reshape(b, t, nkv, dh)
    v = (x @ layer["wv"].astype(x.dtype)).reshape(b, t, nkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if cfg.use_rope and (mixer == WINDOW_MIXER or not cfg.window_layers):
        q, k = rope_halves(q, k, positions, cfg.rope_theta)
    return q, k, v


def heads_out(layer, ctx, gate=None):
    """The heads' outputs ctx [B, T, N * D] through the output product;
    ``gate`` (``cfg.attn_gate``: ``u Wg``, [B, T, N * D]) multiplies them
    by its sigmoid first, elementwise, in float32."""
    if gate is not None:
        with trace_span("attn.gate"):
            ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(ctx.dtype)
    return ctx @ layer["wo"].astype(ctx.dtype)


def kv_attend(layer, q, k_ctx, v_ctx, q_pos, block: int = 1,
              scale: float | None = None, window: int = 0, gate=None):
    """Causal attention of T queries a row over a context of K/V rows.

    q: [B, T, N, D]; k_ctx / v_ctx: [B, N_kv, S, D], row s the K/V of
    position s; q_pos: [B, T] the queries' positions, consecutive along
    T (a query sees s <= its position: rows past it may hold anything).
    ``block`` (static, a power of two; ``MoEConfig.attn_block``): a query
    sees up to the END of its block of that many positions,
    ``s <= q_pos | (block - 1)``; 1 is the causal mask, letter for letter
    (a span of a flash tile then starts at a whole block).  ``scale``: of
    the scores, ``D ** -0.5`` unless given (``MoEConfig.
    attention_multiplier``).  ``window`` (static; 0: none): a query sees
    the last ``window`` keys alone, ``q_pos - window < s <= q_pos``; the
    context's row 0 may then be any position, with ``q_pos`` counted from
    it (a window is the same from wherever it is counted).  ``gate``:
    :func:`heads_out`'s.
    Blockwise through :func:`flash_span_attention` where
    :func:`span_attention_arm` says so (a long span on a TPU; a query
    head reads its K/V head, nothing is repeated), as float32 logits
    ``[B, N, T, S]`` in plain XLA elsewhere: the form the kernel is held
    against.  Returns the block's attention output [B, T, H]."""
    b, t, nh, dh = q.shape
    dt = q.dtype
    if scale is None:
        scale = dh ** -0.5
    if span_attention_arm(t, k_ctx.shape[2], nh, (dh,), dh, dt) == "flash":
        with trace_span("attn.kv_prefill"):
            ctx = _flash_span_ctx((q,), (k_ctx.astype(dt),),
                                  v_ctx.astype(dt), q_pos, scale, block,
                                  **({"window": window} if window else {}))
        return heads_out(layer, ctx, gate)
    with trace_span("attn.kv_prefill"):
        if block > 1:
            q_pos = q_pos | (block - 1)
        if k_ctx.shape[1] != nh:  # GQA: repeat kv heads
            rep = nh // k_ctx.shape[1]
            k_ctx = jnp.repeat(k_ctx, rep, axis=1)
            v_ctx = jnp.repeat(v_ctx, rep, axis=1)
        logits = jnp.einsum(
            "bntd,bnsd->bnts", q.transpose(0, 2, 1, 3), k_ctx,
            preferred_element_type=jnp.float32) * scale
        s_pos = jnp.arange(k_ctx.shape[2])[None, None, None, :]
        mask = s_pos <= q_pos[:, None, :, None]
        if window:
            mask &= s_pos > q_pos[:, None, :, None] - window
        probs = jax.nn.softmax(jnp.where(mask, logits, NEG_INF),
                               axis=-1).astype(dt)
        ctx = jnp.einsum(
            "bnts,bnsd->bntd", probs, v_ctx,
            preferred_element_type=jnp.float32
        ).transpose(0, 2, 1, 3).reshape(b, t, nh * dh).astype(dt)
    return heads_out(layer, ctx, gate)


def store_kv(pages, li: int, span_kv, page_ids, rows):
    """Scatter a span's K (or V) rows into layer ``li`` of its pool.
    pages: [L, P, N_kv, page, D]; span_kv: [B, T, N_kv, D]; page_ids /
    rows: [B, T] int32 (inactive slots and positions past a slot's
    context pass the scratch page: duplicate scratch writes race, but
    scratch rows are never read back with non-zero weight; a rejected
    draft's rows are overwritten before any causal mask exposes them).
    ``rows`` None: the span fills WHOLE pages (a prefill chunk); page_ids
    is then [B, T // page], one id a page.  A pool of packed heads
    (``MoEConfig.kv_pool_rows``: [L, P, N_kv / p, page, p * D]) takes the
    span's heads p to a row, side by side."""
    span_kv = span_kv.reshape(*span_kv.shape[:2], pages.shape[2],
                              pages.shape[4])
    if rows is None:
        _, _, nkv, d = span_kv.shape
        whole = span_kv.reshape(-1, pages.shape[3], nkv, d)
        return pages.at[li, page_ids.reshape(-1)].set(
            whole.transpose(0, 2, 1, 3).astype(pages.dtype))
    # the advanced indices at axes 0 and 2 are split by the head-axis
    # slice, so numpy semantics front their broadcast dims: the result
    # aligns with span_kv exactly
    return pages.at[li].set(
        pages[li].at[page_ids, :, rows, :].set(span_kv))


def gather_ctx(pool, li: int, block_tables, d: int | None = None):
    """Each slot's context window from layer ``li`` of a K (or V) pool.

    pool: ``[L, P, N_kv, page, D]``; block_tables: ``[B, n]`` page ids
    (already sliced to the bucketed page count).  Layer and pages are
    indexed in ONE gather, as :func:`gather_latent` does: ``pool[li]`` as
    a value of its own is a copy of the layer's pool on the chip.
    Returns ``[B, N_kv, n * page, D]`` — rows past a request's length are
    scratch/garbage and MUST be masked by the caller's length mask.  ``d``:
    the heads' width where the pool packs several to a row (pool
    ``[L, P, N_kv / p, page, p * d]``); the heads come back apart."""
    b, n = block_tables.shape
    g = pool[li, block_tables]                 # [B, n, N_kv, page, D]
    _, _, rows, page, width = g.shape
    ctx = g.transpose(0, 2, 1, 3, 4).reshape(b, rows, n * page, width)
    if d in (None, width):
        return ctx
    return ctx.reshape(b, rows, n * page, width // d, d).transpose(
        0, 1, 3, 2, 4).reshape(b, rows * (width // d), n * page, d)


def kv_paged_attention(layer, x, cfg, pools, li, pos, write, block_tables,
                       mixer: str = "mha"):
    """THE K/V attention of every cached path, with
    :func:`mla_paged_attention`'s contract: project a span of T tokens a
    slot, write its K and V rows to layer ``li``'s pages, attend over the
    context.  Two arms by what the shapes and the backend say
    (:func:`kv_attention_arm`): a short span over a paged pool on a TPU
    reads each slot's own pages in place (:func:`paged_decode_attention`);
    everything else stores, gathers the context (layer and pages in one
    gather of the whole pool) and attends through
    :func:`kv_attend` (:func:`store_kv`, :func:`gather_ctx`; in plain
    XLA, the form the kernel is held against, or for a long span on a
    TPU blockwise: :func:`span_attention_arm`).  Under
    ``cfg.attn_block`` > 1 every arm masks by blocks (:func:`kv_attend`).

    x: [B, T, H] normed; pools: the ``(k_pages, v_pages)`` pair, each
    [L, P, N_kv, page, D], or None for a whole prompt at once (the
    context is the span itself); pos: [B, T] absolute positions,
    consecutive along T; write: ``(page_ids, rows)``, each [B, T], or
    ``(page_ids [B, T // page], None)`` for a span of whole pages;
    block_tables: [B, n].  ``mixer`` "swa": a WINDOW layer
    (``cfg.attn_window``), whose ``pools`` are the window pools and whose
    ``block_tables`` may be ``(tables [B, n], base [B])``: the pages a
    window reaches alone, column 0 the page that holds position
    ``base`` (a whole page; a plain array counts from 0).  Every arm then
    masks the keys behind the window and reads no page wholly behind it.
    Under ``cfg.attn_gate`` the heads' outputs pass :func:`heads_out`'s
    gate.  Returns (attention output [B, T, H], the
    pools, the span's ``(k, v)`` rows laid out as a context, each
    [B, N_kv, T, D])."""
    q, k, v = kv_project(layer, x, cfg, pos, mixer)
    span = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    block = cfg.attn_block
    scale = cfg.attention_multiplier            # None: head_dim ** -0.5
    window = {"window": cfg.attn_window} if mixer == WINDOW_MIXER else {}
    if window and isinstance(block_tables, tuple):
        block_tables, base = block_tables
        pos = pos - base[:, None]               # counted from the table's
    gate = x @ layer["wg"].astype(x.dtype) if cfg.attn_gate else None
    if pools is None:
        return (kv_attend(layer, q, *span, pos, block, scale, gate=gate,
                          **window), pools, span)
    rows, page, width = pools[0].shape[2:]       # the heads as stored
    # (under a block mask the kernel knows a span of ONE block or TWO: a
    # longer span over a cache whose page it fits, generate()'s prefill
    # over a short dense cache, keeps the gather arm)
    if (write[1] is not None
            and (block == 1 or q.shape[1] in (block, 2 * block))
            and kv_attention_arm(q.shape[1], page, rows, width,
                                 pools[0].dtype) == "paged_kernel"):
        with trace_span("attn.kv_decode"):
            ctx, pools = paged_decode_attention(
                q, (k, v), pools, li, block_tables, pos[:, 0], write,
                scale=scale, block=block,
                interpret=jax.default_backend() != "tpu", **window)
        return heads_out(layer, ctx, gate), pools, span
    with trace_span("attn.kv_prefill"):
        pools = (store_kv(pools[0], li, k, *write),
                 store_kv(pools[1], li, v, *write))
        k_ctx = gather_ctx(pools[0], li, block_tables, q.shape[-1])
        v_ctx = gather_ctx(pools[1], li, block_tables, q.shape[-1])
    return (kv_attend(layer, q, k_ctx, v_ctx, pos, block, scale, gate=gate,
                      **window), pools, span)


#: the registered scope a layer's token mixer part runs under, by its
#: kind: everything of the part (its norm, projections, the mixer itself,
#: the output product, the residual join) is that kind's time in a trace
#: (``observe --device``); the mixer's two forms (``attn.<kind>_prefill`` /
#: ``_decode``) and the kernels open their own scopes inside it
MIXER_SPANS = {"mha": "attn.kv", WINDOW_MIXER: "attn.kv", "mla": "attn.mla",
               "kda": "attn.kda", "conv": "attn.conv", "ssm": "attn.ssm"}


class ByKind(NamedTuple):
    """What a model with window layers hands a layer where a model without
    hands ONE value (the write targets, the block tables): the full
    layers' and the window layers', each in its pool's own page ids."""

    full: object
    window: object


#: the mixers of ``config.STATE_MIXERS``: each takes ``(layer, x, cfg,
#: *its per-slot arrays or Nones, its index among the state layers, valid,
#: slots, fresh)`` and returns ``(out, *the arrays, the rows' final state
#: as a tuple)``
_STATE_MIXERS = {"kda": kda_attention, "conv": conv_attention,
                 "ssm": ssm_attention}


def paged_attention(layer, x, cfg, pools, li, pos, write, block_tables, *,
                    absorbed: bool, valid=None, slots=None, fresh=None):
    """Layer ``li``'s token mixer over the cache, by ``cfg.mixers[li]``:
    :func:`kv_paged_attention`, :func:`mla_paged_attention` (which alone
    reads ``absorbed``) or a mixer that keeps its state by slot
    (``_STATE_MIXERS``: they alone read ``valid``, ``slots`` and
    ``fresh``, and neither positions nor pages).  pools: the cache's
    arrays as a tuple, or None: the paged pools first (a K/V pair or the
    one latent pool, holding the layers of ``cfg.cache_layers``; then,
    where the config has window layers, THEIR K/V pair, holding the
    layers of ``cfg.window_layers``), then,
    where the config has state layers, what they keep by slot
    (``cfg.slot_state``, holding the layers of ``cfg.state_layers``).
    ``write`` / ``block_tables`` may each be a :class:`ByKind`: a window
    layer takes its ``window``, any other its ``full`` (a plain value,
    the dense cache of ``generate()``, serves both).
    Returns (the block's output, the pools, the span's rows: one entry
    for each array of the cache, None for those this layer does not
    own)."""
    mixer = cfg.mixers[li]
    n_own = 1 if cfg.attention_kind == "mla" else 2   # a kind's paged pools
    n_paged = n_own * (1 + bool(cfg.window_layers))
    n_state = len(cfg.slot_state)
    if mixer in STATE_MIXERS:
        kept = (None,) * n_state if pools is None else pools[n_paged:]
        out, *kept, final = _STATE_MIXERS[mixer](
            layer, x, cfg, *kept, cfg.state_layers.index(li), valid, slots,
            fresh)
        return (out, None if pools is None
                else pools[:n_paged] + tuple(kept),
                (None,) * n_paged + final)
    # a window layer owns an index of the SECOND pair of pools and takes
    # its own kind's write targets and tables
    windowed = mixer == WINDOW_MIXER
    at = n_own * windowed
    ci = (cfg.window_layers if windowed else cfg.cache_layers).index(li)
    kind = lambda v: v[windowed] if isinstance(v, ByKind) else v
    write, block_tables = kind(write), kind(block_tables)
    paged = None if pools is None else pools[at:at + n_own]
    if cfg.attention_kind != "mla":
        out, paged, span = kv_paged_attention(layer, x, cfg, paged, ci, pos,
                                              write, block_tables, mixer)
    else:
        out, pool, latent = mla_paged_attention(
            layer, x, cfg, None if pools is None else paged[0], ci, pos,
            write, block_tables, absorbed=absorbed)
        paged, span = None if pools is None else (pool,), (latent,)
    rows = (None,) * at + span + (None,) * (n_paged - at - n_own)
    return (out, None if pools is None
            else pools[:at] + paged + pools[at + n_own:],
            rows + (None,) * n_state)


# ----------------------------------------------------------------------
# Paged decode attention kernel: a short span over each slot's OWN pages
# ----------------------------------------------------------------------
#
# ``gather_ctx`` + ``kv_attend`` materialise every slot's context at the
# batch's bucket ([B, N_kv, n * page, D], K and V, a layer), and with a
# query or five a head the chip's compiler turns the scores into an f32
# copy of all of it; ``gather_latent`` + the absorbed ``mla_attend`` do
# the same to a latent pool ([B, n * page, C], and a relayout of it).
# This kernel reads the pools where they lie: slot b's block table and
# position arrive in SMEM before the body runs, the pages that hold
# positions [0, pos_b) come into VMEM in blocks, one [N_kv, page, D] DMA
# a page a pool (all heads of a page are contiguous in
# ``[L, P, N_kv, page, D]``), double-buffered, and an online softmax runs
# over them.  The span's own rows are operands: they are attended to from
# VMEM and written into the slot's page by the kernel itself (the page is
# read, its rows replaced, and written back: the pools are aliased to the
# outputs and no XLA instruction touches them, so they keep their layout
# and are never copied).
#
# ONE body, two kinds of pool.  A K/V model hands it a K pool and a V pool
# (``fm_paged_decode``).  An MLA model hands it its ONE latent pool as
# ``[L, P, 1, page, R]`` (``fm_latent_decode``): a token's row is the key
# of every head (multi-query attention over R-wide keys, the queries in
# the absorbed form) and its first ``v_width`` columns are the value, so
# scores and weighted sums read the same block of VMEM.

#: context positions a block of the kernel holds at least (the scores'
#: lanes), and the bytes of context (all pools) it holds at most: 128
#: positions of 16 K and V heads of 128, 512 latent rows of 640
_KV_BLOCK = LANE
_KV_BLOCK_BYTES = 1024 * 1024

#: VMEM the kernel's double-buffered context blocks may take
_KV_VMEM_BUDGET = 8 * 1024 * 1024



def _decode_block(n_kv: int, d: int, dtype, pools: int) -> tuple[int, int]:
    """(context positions a block of the kernel holds, the bytes of one
    position over all pools): the largest power of two of positions
    within ``_KV_BLOCK_BYTES``, ``_KV_BLOCK`` at least."""
    pos_bytes = pools * n_kv * d * jnp.dtype(dtype).itemsize
    fit = max(_KV_BLOCK, _KV_BLOCK_BYTES // pos_bytes)
    return 1 << (fit.bit_length() - 1), pos_bytes


def kv_attention_arm(t: int, page: int, n_kv: int, d: int, dtype,
                     pools: int = 2) -> str:
    """The arm a cached attention layer takes for a span of ``t`` rows a
    slot over ``pools`` pools of pages ``[n_kv, page, d]`` (a K and a V
    pool; or ONE latent pool, whose page is ``[1, page, R]``):
    ``"paged_kernel"`` (:func:`paged_decode_attention`) for a span shorter
    than a page over pages that tile a VMEM block (whole packed tiles of
    ``dtype``, a divisor of the block, full lanes, two blocks within the
    budget) on a TPU; ``"gather"`` (store, gather the context, attend in
    plain XLA) for everything else: a chunk, the dense cache's one
    ``T_max``-row page, rows that are no whole lanes, any other backend.
    :func:`kv_paged_attention`, :func:`mla_paged_attention` and the
    engine's records ask this one function."""
    rows = 32 // jnp.dtype(dtype).itemsize      # of a packed (rows, 128) tile
    block, pos_bytes = _decode_block(n_kv, d, dtype, pools)
    fits = (t < page and block % page == 0 and page % rows == 0
            and d % LANE == 0 and 2 * block * pos_bytes <= _KV_VMEM_BUDGET)
    return ("paged_kernel" if fits and jax.default_backend() == "tpu"
            else "gather")


def paged_decode_block_pages(page: int, n_tab: int, n_kv: int, d: int,
                             dtype, pools: int = 2) -> int:
    """Pages a block of the kernel reads, under tables ``n_tab`` pages
    wide (a slot's context is read rounded up to this)."""
    block, _ = _decode_block(n_kv, d, dtype, pools)
    return max(1, min(block // page, n_tab))


def _paged_decode_kernel(li_ref, tab_ref, pos_ref, wpage_ref, wrow_ref,
                         q_ref, *refs, n_pools, t, rep, page, bp, n_tab,
                         scale, block=1, window=0):
    """Grid: (B,), one slot a step.  li_ref: [1] the layer; tab_ref /
    pos_ref / wpage_ref / wrow_ref: the block tables, positions and write
    targets, flat.  q_ref: [1, N_kv, R, D], row ``t * rep + g`` the query
    of span column t and head ``h * rep + g``.  ``refs``, ``n_pools`` of
    each: the span's rows [1, N_kv, Tp, D]; the pools in HBM, read
    through these; then o_ref [1, N_kv, R, Dv]; the pools' aliases, which
    the span is written through; the context blocks [2, N_kv, bp * page,
    D]; the one or two pages the span's rows fall into [2, N_kv, page,
    D]; and the blocks' and the pages' DMA semaphores.  Keys are the
    first pool's rows and values the first Dv columns of the last
    pool's: K and V, or one latent row as both.  ``window`` > 0: span
    column c (position ``pos + c``) sees the keys ``j > pos + c - window``
    alone: the walk starts at the block that holds the first of them and
    fetches none before it."""
    n = n_pools
    spans, hbm = refs[:n], refs[n:2 * n]
    o_ref, out_hbm = refs[2 * n], refs[2 * n + 1:3 * n + 1]
    bufs, wbufs = refs[3 * n + 1:4 * n + 1], refs[4 * n + 1:5 * n + 1]
    sems, wsems = refs[5 * n + 1:]
    b = pl.program_id(0)
    li, pos = li_ref[0], pos_ref[b]
    nkv, r_pad, d = q_ref.shape[1:]
    dv = o_ref.shape[-1]
    s_blk = bp * page
    n_blocks = (pos + s_blk - 1) // s_blk
    # the first block the window touches (0 without one)
    blk0 = (jnp.maximum(pos - window + 1, 0) // s_blk) if window else 0

    def values(rows):
        return rows if dv == d else rows[..., :dv]

    def block_dmas(blk, slot, act):
        """Start, or wait for, the copies of block ``blk``'s pages into
        buffer ``slot``: a loop over the pages, one turn a page.  Written
        out (32 copies of a latent block at three places) the kernel was a
        quarter faster (0.317 against 0.430 ms a call at the longctx
        cell's shapes) and every process paid for it in ``setup_s`` (+12
        %: a decode program a context bucket, each traced and lowered)."""
        def page_j(j, _):
            pid = tab_ref[b * n_tab + jnp.minimum(blk * bp + j, n_tab - 1)]
            rows = pl.ds(j * page, page)
            for i in range(n):
                dma = pltpu.make_async_copy(
                    hbm[i].at[li, pid], bufs[i].at[slot, :, rows, :],
                    sems.at[i, slot])
                dma.start() if act == "start" else dma.wait()

        jax.lax.fori_loop(0, bp, page_j, None)

    # the page the span starts in and, where the span crosses a page
    # edge, the next: rows the span does not write are kept
    page_a = wpage_ref[b * t]
    page_b = wpage_ref[b * t + t - 1]
    two = page_b != page_a

    def page_dmas(page_id, i, *, store):
        for j in range(n):
            ends = (wbufs[j].at[i], out_hbm[j].at[li, page_id])
            yield pltpu.make_async_copy(*(ends if store else ends[::-1]),
                                        wsems.at[i, j])

    for dma in page_dmas(page_a, 0, store=False):
        dma.start()

    @pl.when(two)
    def _():
        for dma in page_dmas(page_b, 1, store=False):
            dma.start()

    @pl.when(n_blocks > blk0)
    def _():
        block_dmas(blk0, jax.lax.rem(blk0, 2) if window else 0, "start")

    q = q_ref[0]                                            # [N_kv, R, D]
    # products of bf16 operands are exact in f32 as they are; Mosaic
    # refuses them an ambient ``default_matmul_precision("highest")``
    f32 = dict(preferred_element_type=jnp.float32,
               precision=None if q.dtype == jnp.float32
               else jax.lax.Precision.DEFAULT)

    def attend(carry, s, v_rows):
        """One online-softmax step: masked scores s [h, r, c] over the
        values v_rows [h, c, Dv]."""
        m_prev, l_prev, acc = carry
        m = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m)
        alpha = jnp.exp(m_prev - m)
        return (m, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + jnp.einsum(
                    "hrc,hcd->hrd", p.astype(v_rows.dtype), v_rows, **f32))

    def body(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _():
            block_dmas(blk + 1, 1 - slot, "start")

        block_dmas(blk, slot, "wait")
        s = jnp.einsum("hrd,hcd->hrc", q, bufs[0][slot], **f32) * scale
        col = blk * s_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        seen = col < pos
        if window:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen &= col > pos + row // rep - window
        return attend(carry, jnp.where(seen, s, NEG_INF),
                      values(bufs[-1][slot]))

    carry = jax.lax.fori_loop(
        blk0, n_blocks, body,
        (jnp.full((nkv, r_pad, 1), NEG_INF, jnp.float32),
         jnp.zeros((nkv, r_pad, 1), jnp.float32),
         jnp.zeros((nkv, r_pad, dv), jnp.float32)))

    # the span's own rows, causal among themselves: query row r (column
    # r // rep of the span) sees span row c iff c * rep <= r; under a
    # block-causal model (the span whole blocks, its first row at a whole
    # block) iff c's block is not past its own: one block sees all of
    # itself, of two the first never sees the second
    new = [span[0] for span in spans]                       # [N_kv, Tp, D]
    s = jnp.einsum("hrd,hcd->hrc", q, new[0], **f32) * scale
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    seen = ((col // block <= row // (rep * block)) if block > 1
            else (col * rep <= row)) & (col < t)
    if window:
        seen &= col > row // rep - window
    _, l, acc = attend(carry, jnp.where(seen, s, NEG_INF), values(new[-1]))
    o_ref[0] = (acc / l).astype(o_ref.dtype)

    # the span's rows into their pages
    for dma in page_dmas(page_a, 0, store=False):
        dma.wait()

    @pl.when(two)
    def _():
        for dma in page_dmas(page_b, 1, store=False):
            dma.wait()

    at = jax.lax.broadcasted_iota(jnp.int32, wbufs[0].shape[1:], 1)
    for c in range(t):
        which = (wpage_ref[b * t + c] != page_a).astype(jnp.int32)
        here = at == wrow_ref[b * t + c]
        for buf, rows in zip(wbufs, new):
            buf[which] = jnp.where(
                here, rows[:, c:c + 1, :].astype(jnp.float32),
                buf[which].astype(jnp.float32)).astype(buf.dtype)
    for dma in page_dmas(page_a, 0, store=True):
        dma.start()

    @pl.when(two)
    def _():
        for dma in page_dmas(page_b, 1, store=True):
            dma.start()
        for dma in page_dmas(page_b, 1, store=True):
            dma.wait()

    for dma in page_dmas(page_a, 0, store=True):
        dma.wait()


@functools.partial(jax.jit, static_argnames=("v_width", "scale",
                                             "block_pages", "block",
                                             "window", "interpret"))
def paged_decode_attention(q, span, pools, li, block_tables, pos, write, *,
                           v_width: int | None = None,
                           scale: float | None = None,
                           block_pages: int | None = None,
                           block: int = 1, window: int = 0,
                           interpret: bool = False):
    """Causal attention of a short span a slot over the slot's own pages,
    read in place, and the span's rows written into them.  Jitted with
    the layer ``li`` as an operand: the layers of a program share ONE
    traced and lowered kernel (lowered once a layer, the kernels were a
    second of every decode program's set-up, cache or no cache).

    q: [B, T, N, D]; span: the span's rows, one [B, T, N_kv, D] a pool
    (T < page); pools: a ``(k_pages, v_pages)`` pair, or ONE pool whose
    rows are keys and, in their first ``v_width`` columns, values (an MLA
    model's latent pool: N_kv = 1, ``q`` in the absorbed form), each
    [L, P, N_kv, page, D], or a pair that packs p heads narrower than a
    lane tile to a row ([L, P, N_kv / p, page, p * D]:
    ``MoEConfig.kv_pool_rows``); block_tables: [B, n]; pos: [B] the position of
    each slot's first span row, the pool holding positions before it;
    write: ``(page_ids, rows)``, each [B, T], where the span's rows go
    (consecutive rows: at most two pages a slot); scale: of the scores,
    ``D ** -0.5`` unless given; block: 1, or the span is one block or two
    of a block-causal model (T == block or 2 * block, ``pos`` a whole
    block: a row of the span sees its own block whole and the block
    before it, never the block after).  Returns (the heads' outputs
    [B, T, N * Dv], the pools).  What :func:`store_kv`,
    :func:`gather_ctx` and the softmax of :func:`kv_attend` give (or
    :func:`store_latent`, :func:`gather_latent` and the absorbed
    :func:`mla_attend` up to the latent sums), with f32 scores, statistics
    and accumulator, the probabilities rounded to the pool's dtype before
    the product with the values."""
    n_pools, dt = len(pools), pools[0].dtype
    nkv, page, width = pools[0].shape[2:]
    if block > 1 and q.shape[1] not in (block, 2 * block):
        raise NotImplementedError(
            f"a span of {q.shape[1]} rows under a block mask of {block}: "
            f"the kernel's in-span mask knows a span of ONE block or TWO")
    pack = width // q.shape[-1]
    if pack > 1:
        # a pool of packed heads: the kernel is handed rows of whole
        # lanes.  Row r holds heads r * pack .. + pack - 1 side by side,
        # so a query of head r * pack + p stands in the p-th part of a
        # row-wide query, zeros beside it (its scores are its own head's),
        # and its output is the p-th part of the row-wide sum.  The
        # products are pack x the narrow heads': nothing, beside the
        # context's bytes.
        b, t, nh, d = q.shape
        apart = jnp.eye(pack, dtype=q.dtype)
        wide = jnp.einsum(
            "btrpgd,pq->btrpgqd", q.reshape(b, t, nkv, pack, -1, d), apart)
        out, pools = paged_decode_attention(
            wide.reshape(b, t, nh, width),
            [x.reshape(b, t, nkv, width) for x in span], pools, li,
            block_tables, pos, write, scale=scale or d ** -0.5,
            block_pages=block_pages, block=block, window=window,
            interpret=interpret)
        out = jnp.einsum(
            "btrpgqd,pq->btrpgd",
            out.reshape(b, t, nkv, pack, -1, pack, d), apart)
        return out.reshape(b, t, nh * d), pools
    b, t, nh, d = q.shape
    dv = v_width or d
    rep = nh // nkv
    n_tab = block_tables.shape[1]
    bp = block_pages or paged_decode_block_pages(page, n_tab, nkv, d, dt,
                                                 n_pools)
    tile = 32 // q.dtype.itemsize
    r_pad = -(-t * rep // tile) * tile
    t_pad = -(-t // tile) * tile
    # [B, T, N_kv, rep, D] -> [B, N_kv, T * rep, D], whole tiles of rows
    qh = q.reshape(b, t, nkv, rep, d).transpose(0, 2, 1, 3, 4).reshape(
        b, nkv, t * rep, d)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, r_pad - t * rep), (0, 0)))
    span = [jnp.pad(x.transpose(0, 2, 1, 3),
                    ((0, 0), (0, 0), (0, t_pad - t), (0, 0))).astype(dt)
            for x in span]
    slot_block = lambda rows, width: pl.BlockSpec(
        (1, nkv, rows, width), lambda i, *_: (i, 0, 0, 0),
        memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, *pools = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, n_pools=n_pools, t=t, rep=rep, page=page,
            bp=bp, n_tab=n_tab, scale=scale or d ** -0.5,
            **({"block": block} if block > 1 else {}),
            **({"window": window} if window else {})),
        name="fm_paged_decode" if n_pools == 2 else "fm_latent_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[slot_block(r_pad, d)]
            + [slot_block(t_pad, d)] * n_pools + [hbm] * n_pools,
            out_specs=[slot_block(r_pad, dv)] + [hbm] * n_pools,
            scratch_shapes=[pltpu.VMEM((2, nkv, bp * page, d), dt)] * n_pools
            + [pltpu.VMEM((2, nkv, page, d), dt)] * n_pools
            + [pltpu.SemaphoreType.DMA((n_pools, 2)),
               pltpu.SemaphoreType.DMA((2, n_pools))]),
        out_shape=[jax.ShapeDtypeStruct((b, nkv, r_pad, dv), q.dtype)]
        + [jax.ShapeDtypeStruct(pool.shape, dt) for pool in pools],
        # operands: 5 scalar vectors, q, the span's rows, then the pools
        input_output_aliases={6 + n_pools + i: 1 + i
                              for i in range(n_pools)},
        interpret=interpret,
    )(jnp.asarray(li, jnp.int32).reshape(1), block_tables.reshape(-1), pos,
      write[0].reshape(-1), write[1].reshape(-1), qh, *span, *pools)
    out = out[:, :, :t * rep].reshape(b, nkv, t, rep, dv).transpose(
        0, 2, 1, 3, 4).reshape(b, t, nh * dv)
    return out, tuple(pools)


# ----------------------------------------------------------------------
# Flash attention kernel
# ----------------------------------------------------------------------
#
# Three kernels over [B*N, T, D] arrays, blockwise, one algorithm:
#   forward   s = q k^T * scale (masked);  m, l the running row max and sum
#             of exp(s - m);  o = sum_j exp(s - m) v / l;  lse = m + log l
#   backward  p = exp(s - lse);  delta = rowsum(dO * o);  dp = dO v^T;
#             ds = p (dp - delta) * scale;  dv = p^T dO;  dk = ds^T q;
#             dq = ds k
# Every product runs on the operands' own dtype with a float32 result;
# scale, mask, max, sum, exp, lse, delta and the accumulators are float32;
# p and ds are cast to the other operand's dtype for their products.
# ``fm_flash_fwd`` and ``fm_flash_bwd_dq`` hold a query block and walk the
# K/V blocks, ``fm_flash_bwd_dkv`` holds a K/V block and walks the query
# blocks; it computes s, p and ds TRANSPOSED ([block_k, block_q]), so that
# the row statistics broadcast along sublanes as they are stored ([1, T]
# rows) and p^T dO, ds^T q are plain products.  Under ``causal`` a block
# wholly above the diagonal is skipped AND not fetched: the walked side's
# index map clamps to the nearest block the holder needs, so a skipped
# step names the block already resident (or the one the first live step
# wants) and issues no DMA of its own.  Only blocks the diagonal crosses
# pay for the mask.
#
# The forward kernel has a second launch, ``fm_flash_span``
# (:func:`flash_span_attention`): the serving engine's prefill programs,
# whose queries are a chunk or a whole prompt over a gathered context.
# The same body, tile rule and clamp, with what differs an operand or a
# shape: the context position of the first query row is a scalar in SMEM
# that the body's mask and the K/V index maps read (the diagonal shifts,
# blocks wholly past a query block's last row are neither fetched nor
# computed); the key may come in parts whose products sum (MLA's own
# 128-wide part a head and ONE 64-wide rotary part for all heads); an
# array with fewer heads than the queries is read by every head of its
# group; no log-sum-exp is written.  :func:`span_attention_arm` says
# where :func:`mla_attend` and :func:`kv_attend` take it.

#: rows a side of the tile the rule starts from.  A grid step costs the
#: same whatever it holds (its DMAs' issue, the online softmax's row
#: statistics and the accumulator's rescale), so the tile is as large as
#: :func:`flash_blocks` lets it be: raced on a v5e at [32, 4096, 128]
#: bfloat16 (PERF.md §6, PR 43), a forward call takes 6.3 / 3.1 / 1.5 ms
#: at 256 / 512 / 1024 a side
_FLASH_TILE = 1024


def _flash_vmem(bq: int, bk: int, d: int, isz: int,
                dv: int | None = None) -> int:
    """Bytes a grid step of the costliest of the three kernels keeps in
    VMEM: double-buffered blocks of the query side (q, dO, o or dq) and of
    the K/V side (k, v, dk, dv), the float32 scratch (two lane-wide row
    statistics, the accumulators) and ONE float32 [bq, bk] tile: Mosaic
    streams the chain from scores to the cast probabilities through
    registers and keeps about one copy of the tile (at 1024 x 1024 x 128
    bfloat16 the three kernels compile within 9 MiB and not within 8; at
    512 x 512 within 3 and not 2).  With ``dv`` the forward kernel ALONE
    (the span form, which has no backward) over keys ``d`` and values
    ``dv`` wide: q, k, v and o blocks and one accumulator."""
    if dv is not None:
        blocks = 2 * (bq + bk) * (d + dv) * isz
        return blocks + (2 * bq * LANE + bq * dv) * 4 + bq * bk * 4
    blocks = 2 * (3 * bq + 4 * bk) * d * isz
    scratch = (2 * bq * LANE + (bq + 2 * bk) * d) * 4
    return blocks + scratch + bq * bk * 4


def _flash_side(t: int, target: int) -> int:
    """Largest block of whole lanes that divides ``t`` and is no larger
    than ``target``; ``t`` itself where it has none (one block a side)."""
    return next((b for b in range(min(t, target) // LANE * LANE, 0, -LANE)
                 if t % b == 0), t)


def _flash_params(bq: int, bk: int, d: int, dtype,
                  dv: int | None = None) -> pltpu.CompilerParams:
    """The kernels' VMEM request at a tile: the experts' rule over what a
    step counts (a quarter and 2 MiB on top, Mosaic's default at least)."""
    return _vmem_params(_flash_vmem(bq, bk, d, jnp.dtype(dtype).itemsize,
                                    dv))


def flash_blocks(tq: int, tk: int, d: int, dtype,
                 dv: int | None = None) -> tuple[int, int]:
    """The tile (block_q, block_k) the flash kernels take over ``tq``
    query and ``tk`` K/V rows of width ``d`` (``dv``: the forward kernel
    alone, values ``dv`` wide: :func:`_flash_vmem`): :data:`_FLASH_TILE` a
    side where it divides the side, the larger side halved while the
    kernels would ask for more than Mosaic's default scope of 16 MiB.  A
    wider scope is taken from the VMEM in which XLA keeps arrays of the
    program AROUND the kernel: with 39.5 MiB requested the train step's
    output head lost one and 2 ms a step (PERF.md §6, PR 43)."""
    bq, bk = _flash_side(tq, _FLASH_TILE), _flash_side(tk, _FLASH_TILE)
    while (_flash_params(bq, bk, d, dtype, dv).vmem_limit_bytes
           > _VMEM_DEFAULT):
        if bk >= bq and bk > LANE:
            bk = _flash_side(tk, bk // 2)
        elif bq > LANE:
            bq = _flash_side(tq, bq // 2)
        else:
            break
    return bq, bk


def _flash_step(step, q_start, k_start, block_q, block_k, causal,
                window: int = 0):
    """Run ``step(masked)`` for the tile whose first query row is
    ``q_start`` and first key ``k_start``, if any of it lies on or under
    the diagonal (it is live); the masked form only where some of it lies
    above (the diagonal crosses it).  ``window`` > 0 (a query sees the
    keys ``j > q - window``): a tile wholly behind the first row's window
    is not live either, and ``step(masked, edged)`` pays the window's mask
    only where some of the tile lies behind the LAST row's window (the
    window's edge crosses it)."""
    if not causal:
        step(False)
        return
    live = k_start <= q_start + block_q - 1
    crossed = k_start + block_k - 1 > q_start
    if window:
        live &= k_start + block_k - 1 > q_start - window
        edged = k_start <= q_start + block_q - 1 - window
        for m in (False, True):
            for e in (False, True):
                pl.when(live & (crossed == m) & (edged == e))(
                    functools.partial(step, m, e))
        return
    pl.when(live & crossed)(functools.partial(step, True))
    pl.when(live & jnp.logical_not(crossed))(functools.partial(step, False))


def _nt(a, b):
    """a b^T with a float32 result: [m, d] x [n, d] -> [m, n]."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """a b with a float32 result: [m, k] x [k, n] -> [m, n]."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_keep(q_start, k_start, shape, q_axis, block: int = 1):
    """Where a [.., ..] tile of scores keeps its value: query position >=
    key position; ``q_axis`` is the tile's axis of queries.  ``block`` > 1:
    the END of the query's block of that many positions >= the key's (a
    tile starts at a whole block, so which tiles are live, crossed or
    fetched does not move: :func:`_flash_step`, :func:`_last_live`)."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) + q_start
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) + k_start
    if block > 1:
        qpos = qpos | (block - 1)
    return qpos >= kpos


def _window_keep(q_start, k_start, shape, window: int):
    """Where a [block_q, block_k] tile of scores keeps its value under a
    window: key position > query position - ``window``."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + q_start
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + k_start
    return kpos > qpos - window


def _rows_to_column(row):
    """A [1, block] row of float32 statistics as a lane-wide [block, 128]
    column (every lane the same value), the layout the [block, .] tiles
    broadcast from."""
    return jnp.transpose(jnp.broadcast_to(row, (LANE, row.shape[1])))


def _flash_kernel(*refs, scale, causal, block_q, block_k, parts=1,
                  heads=None, lse=True, block=1, window=0):
    """Grid: (B*N, Tq/block_q, Tk/block_k) — kv innermost, accumulating the
    online softmax in VMEM scratch.  m/l scratch is lane-width (bq, 128)
    holding broadcast copies to keep TPU layouts happy, like the upstream
    flash kernels; the log-sum-exp leaves as a [1, bq] row.

    ``refs``: with ``heads`` (the span form, :func:`flash_span_attention`)
    first pos_ref, [B] in SMEM, the context position of each batch row's
    first query (query row i sees context rows ``s <= pos + i``); then the
    ``parts`` query blocks and the ``parts`` key blocks (the score is the
    sum of the parts' products), v_ref, o_ref, lse_ref (where ``lse``) and
    the scratch m, l, acc.  ``block``: the mask's (:func:`_causal_keep`);
    ``window``: :func:`_flash_step`'s (a row whose window lies wholly
    past a live tile scores it all NEG_INF: its statistics are wiped by
    the rescale once a key it sees arrives, and its own key always does)."""
    if heads is not None:
        pos_ref, *refs = refs
    qs, ks = refs[:parts], refs[parts:2 * parts]
    v_ref, o_ref = refs[2 * parts:2 * parts + 2]
    lse_ref = refs[2 * parts + 2] if lse else None
    m_scr, l_scr, acc_scr = refs[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    if heads is not None:
        q_start = q_start + pos_ref[pl.program_id(0) // heads]
    k_start = ki * block_k

    def step(masked, edged=False):
        s = _nt(qs[0][0], ks[0][0])             # [bq, bk]
        for q_ref, k_ref in zip(qs[1:], ks[1:]):
            s = s + _nt(q_ref[0], k_ref[0])
        s = s * scale
        if masked:
            s = jnp.where(_causal_keep(q_start, k_start, s.shape, 0, block),
                          s, NEG_INF)
        if edged:
            s = jnp.where(_window_keep(q_start, k_start, s.shape, window),
                          s, NEG_INF)
        m_prev = m_scr[:, :1]                   # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)         # [bq, 1]
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc = acc_scr[:] * alpha
        p = p.astype(v_ref.dtype)
        v = v_ref[0]
        if masked and heads is not None:
            # context rows past the last query's position are scratch
            # (anything, a NaN too): their probabilities are 0, and
            # 0 x NaN is NaN, so their values are zeroed as well
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row + k_start < q_start + block_q, v,
                          jnp.zeros_like(v))
        acc_scr[:] = acc + _nn(p, v)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    _flash_step(step, q_start, k_start, block_q, block_k, causal,
                **({"window": window} if window else {}))

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l[:, :1]).astype(o_ref.dtype)
        if lse:
            lse_ref[0] = jnp.transpose(m_scr[:] + jnp.log(l))[:1]


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     lse_scr, delta_scr, dq_scr, *, scale, causal, block_q,
                     block_k):
    """Grid: (B*N, Tq/block_q, Tk/block_k) — kv innermost, as the forward:
    dq of one query block accumulates in VMEM over the K/V blocks it
    attends to."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        lse_scr[:] = _rows_to_column(lse_ref[0])
        delta_scr[:] = _rows_to_column(delta_ref[0])

    q_start = qi * block_q
    k_start = ki * block_k

    def step(masked):
        k = k_ref[0]
        s = _nt(q_ref[0], k) * scale            # [bq, bk]
        if masked:
            s = jnp.where(_causal_keep(q_start, k_start, s.shape, 0),
                          s, NEG_INF)
        p = jnp.exp(s - lse_scr[:, :1])
        dp = _nt(do_ref[0], v_ref[0])
        ds = p * (dp - delta_scr[:, :1]) * scale
        dq_scr[:] = dq_scr[:] + _nn(ds.astype(k.dtype), k)

    _flash_step(step, q_start, k_start, block_q, block_k, causal)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                      block_q, block_k):
    """Grid: (B*N, Tk/block_k, Tq/block_q) — q innermost: dk and dv of one
    K/V block accumulate in VMEM over the query blocks that attend to it.
    Scores, probabilities and ds are [bk, bq] here (see the section's
    head)."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def step(masked):
        q, do = q_ref[0], do_ref[0]
        s = _nt(k_ref[0], q) * scale            # [bk, bq]
        if masked:
            s = jnp.where(_causal_keep(q_start, k_start, s.shape, 1),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])             # the [1, bq] row, by sublane
        dv_scr[:] = dv_scr[:] + _nn(p.astype(do.dtype), do)
        dp = _nt(v_ref[0], do)
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] = dk_scr[:] + _nn(ds.astype(q.dtype), q)

    _flash_step(step, q_start, k_start, block_q, block_k, causal)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False):
    """Blockwise attention. q/k/v: [B, N, T, D] with T % block == 0;
    ``block_q`` / ``block_k`` None: the rule's (:func:`flash_blocks`).

    Differentiable: ``pallas_call`` has no transpose rule, so the forward
    kernel also writes each row's log-sum-exp and the backward is two
    kernels that recompute the probabilities of a tile from it
    (``fm_flash_bwd_dkv``, ``fm_flash_bwd_dq``); no [T, T] array exists
    in either direction."""
    return _flash_ad(q, k, v, causal, scale, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_ad(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)[0]


def _flash_ad_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_ad_bwd(causal, scale, block_q, block_k, interpret, res, dy):
    return _flash_backward(*res, dy, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash_ad.defvjp(_flash_ad_fwd, _flash_ad_bwd)


def _flash_plan(q, k, scale, block_q, block_k):
    """(scale, bq, bk, compiler params) of a launch over q [B, N, Tq, D]
    and k [B, N, Tk, D]."""
    tq, d = q.shape[2:]
    tk = k.shape[2]
    rule = flash_blocks(tq, tk, d, q.dtype)
    bq = min(block_q or rule[0], tq)
    bk = min(block_k or rule[1], tk)
    if tq % bq or tk % bk:
        raise ValueError(f"T ({tq},{tk}) must divide blocks ({bq},{bk})")
    return (scale if scale is not None else d ** -0.5, bq, bk,
            _flash_params(bq, bk, d, q.dtype))


def _last_live(i, bq: int, bk: int, q_pos0=None):
    """The last K/V block that query block ``i`` is live for under the
    causal mask, its first row at context position ``q_pos0`` (None: 0)."""
    end = (i + 1) * bq - 1
    return (end if q_pos0 is None else end + q_pos0) // bk


def _flash_specs(bq, bk, d, causal, nk, q_inner):
    """Block specs (query side [1, bq, d], K/V side [1, bk, d], a row
    statistic [1, 1, bq]) of a grid (h, holder, walker): the walker is the
    K/V block when ``q_inner`` is false, the query block when true.  The
    walker is clamped to the blocks the holder's tile is live for."""
    if q_inner:
        first = (lambda j: (j * bk) // bq) if causal else (lambda j: 0)
        qb = lambda h, j, i: jnp.maximum(i, first(j))
        kb = lambda h, j, i: j
    else:
        last = ((lambda i: _last_live(i, bq, bk)) if causal
                else (lambda i: nk - 1))
        qb = lambda h, i, j: i
        kb = lambda h, i, j: jnp.minimum(j, last(i))
    spec = lambda shape, imap: pl.BlockSpec(shape, imap,
                                            memory_space=pltpu.VMEM)
    return (spec((1, bq, d), lambda *g: (g[0], qb(*g), 0)),
            spec((1, bk, d), lambda *g: (g[0], kb(*g), 0)),
            spec((1, 1, bq), lambda *g: (g[0], 0, qb(*g))))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "scale"),
)
def _flash_forward(q, k, v, *, causal: bool, scale: float | None,
                   block_q: int | None, block_k: int | None,
                   interpret: bool):
    """(o [B, N, Tq, D], lse [B*N, 1, Tq] float32)."""
    b, n, tq, d = q.shape
    tk = k.shape[2]
    scale, bq, bk, params = _flash_plan(q, k, scale, block_q, block_k)
    q_spec, kv_spec, row_spec = _flash_specs(bq, bk, d, causal, tk // bk,
                                             q_inner=False)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk,
        ),
        name="fm_flash_fwd",
        grid=(b * n, tq // bq, tk // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b * n, tq, d), q.dtype),
                   jax.ShapeDtypeStruct((b * n, 1, tq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(q.reshape(b * n, tq, d), k.reshape(b * n, tk, d),
      v.reshape(b * n, tk, d))
    return out.reshape(b, n, tq, d), lse


#: float32 scores ``[N, t, s]`` the flash arm starts from.  Under it
#: XLA's fusions over the scores keep up with the kernel: raced on a v5e
#: (PERF.md §6, PR 44; a layer's attention with its output product, ms,
#: XLA | kernel), 32 MiB of them (32 heads x 512 x 512) 0.241 | 0.269
#: with MLA's keys, 0.247 | 0.252 and 0.228 | 0.242 with 128- and 64-wide
#: heads; 64 MiB 0.995 | 0.692 (64 x 512 x 512) and 0.339 | 0.223
#: (16 x 1024 x 1024); from there the XLA form grows with the scores'
#: bytes (4.90 | 1.75 at 512 MiB) and the kernel with the live tiles
_SPAN_SCORE_BYTES = 64 << 20


def span_attention_arm(t: int, s: int, heads: int,
                       k_widths: tuple[int, ...], v_width: int,
                       dtype) -> str:
    """The arm the gather arm's attention takes for a span of ``t`` rows a
    slot over a context of ``s`` rows at ``heads`` query heads, the keys
    in parts ``k_widths`` wide and the values ``v_width``: ``"flash"``
    (:func:`flash_span_attention`) on a TPU for a span and a context of
    whole 128-row blocks whose float32 scores would take
    :data:`_SPAN_SCORE_BYTES` or more, key parts and values of whole
    lanes or half a lane tile (MLA's shared 64-wide rotary key; a
    narrower block is no whole sublane tile of its transpose) and a dtype
    the matrix unit takes; ``"xla"`` (the float32 logits ``[B, N, t, s]``
    of :func:`mla_attend` / :func:`kv_attend`) for everything else: a
    decode or verify span, a short prompt, one that is no whole blocks,
    any other backend.  :func:`mla_attend`, :func:`kv_attend` and the
    engine's records ask this one function."""
    fits = (t % LANE == 0 and s % LANE == 0 and s >= t
            and heads * t * s * 4 >= _SPAN_SCORE_BYTES
            and all(w % (LANE // 2) == 0 for w in (*k_widths, v_width))
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))
    return "flash" if fits and jax.default_backend() == "tpu" else "xla"


def attention_widths(cfg) -> tuple[tuple[int, ...], int]:
    """(the widths of the key's parts, the value's width) of ``cfg``'s
    attention layers, as :func:`span_attention_arm` takes them: what
    :func:`mla_attend`'s plain form and :func:`kv_attend` hand the
    kernel."""
    if cfg.attention_kind == "mla":
        return ((cfg.qk_nope_head_dim, cfg.qk_rope_head_dim),
                cfg.v_head_dim)
    return (cfg.resolved_head_dim,), cfg.resolved_head_dim


@functools.partial(jax.jit, static_argnames=("scale", "block", "window",
                                             "interpret"))
def flash_span_attention(q, k, v, q_pos0, *, scale: float, block: int = 1,
                         window: int = 0, interpret: bool = False):
    """Causal attention of a span of queries over a context that starts
    before it: the forward flash kernel (``_flash_kernel``: the training
    call's body, tile rule and clamped index maps) with the position of
    the first query as an OPERAND.  Jitted: the layers of a program share
    ONE traced and lowered kernel a shape.

    q: the query's parts, each [B, N, Tq, d_i]; k: the key's parts, each
    [B, N_i, Tk, d_i] with N_i a divisor of N (head h reads K/V head
    ``h // (N / N_i)``: N for a head's own keys, fewer for grouped
    queries, 1 for a part every head shares, MLA's rotary key); the
    score is the sum of the parts' products times ``scale``; v:
    [B, N_v, Tk, Dv]; q_pos0: [B] int32, query row i of batch row b sees
    context rows ``s <= q_pos0[b] + i``; rows past that may hold anything
    (K/V blocks wholly past a query block's last row are neither fetched
    nor computed, only blocks the shifted diagonal crosses are masked).
    Products on the operands' dtype with float32 results, float32 scale,
    mask, statistics and accumulator, the probabilities cast to ``v``'s
    dtype.  ``block`` > 1 (a power of two that divides the tile; q_pos0 a
    whole block): a query sees up to the end of its block of that many
    positions, ``s <= (q_pos0[b] + i) | (block - 1)``; only the tiles the
    diagonal crosses mask differently.  ``window`` > 0: a query sees the
    last ``window`` context rows alone, ``s > q_pos0[b] + i - window``: K/V
    blocks wholly behind a query block's FIRST row's window are neither
    fetched nor computed (the index maps clamp from below as they clamp
    from above), and only the blocks the window's edge crosses pay its
    mask.  No [Tq, Tk] array exists.  Returns
    [B, N, Tq, Dv]."""
    b, n, tq, _ = q[0].shape
    tk, dv = v.shape[2:]
    lanes = lambda w: -(-w // LANE) * LANE      # a block's width in VMEM
    dk = sum(lanes(part.shape[-1]) for part in k)
    bq, bk = flash_blocks(tq, tk, dk, v.dtype, lanes(dv))

    def q_side(width):
        return pl.BlockSpec((1, bq, width), lambda h, i, j, pos: (h, i, 0),
                            memory_space=pltpu.VMEM)

    def kv_side(x):
        rep = n // x.shape[1]       # query heads a head of this array
        if window:
            # clamped from below too: the first block query block i's
            # first row still sees
            return pl.BlockSpec(
                (1, bk, x.shape[-1]),
                lambda h, i, j, pos: (h // rep, jnp.clip(
                    j, jnp.maximum(pos[h // n] + i * bq - window + 1, 0)
                    // bk, _last_live(i, bq, bk, pos[h // n])), 0),
                memory_space=pltpu.VMEM)
        return pl.BlockSpec(
            (1, bk, x.shape[-1]),
            lambda h, i, j, pos: (h // rep, jnp.minimum(
                j, _last_live(i, bq, bk, pos[h // n])), 0),
            memory_space=pltpu.VMEM)

    flat = lambda x: x.reshape(-1, *x.shape[2:])
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=True, block_q=bq, block_k=bk,
            parts=len(q), heads=n, lse=False,
            **({"block": block} if block > 1 else {}),
            **({"window": window} if window else {})),
        name="fm_flash_span",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * n, tq // bq, tk // bk),
            in_specs=[q_side(part.shape[-1]) for part in q]
            + [kv_side(part) for part in k] + [kv_side(v)],
            out_specs=q_side(dv),
            scratch_shapes=[
                pltpu.VMEM((bq, LANE), jnp.float32),
                pltpu.VMEM((bq, LANE), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * n, tq, dv), q[0].dtype),
        compiler_params=_flash_params(bq, bk, dk, v.dtype, lanes(dv)),
        interpret=interpret,
    )(q_pos0.astype(jnp.int32), *map(flat, q), *map(flat, k), flat(v))
    return out.reshape(b, n, tq, dv)


def _flash_span_ctx(q, k, v, q_pos, scale: float, block: int = 1,
                    window: int = 0):
    """:func:`flash_span_attention` as the cached attention calls it: the
    query's parts laid out [B, T, N, d_i], q_pos [B, T] consecutive along
    T, interpreted off a TPU.  Returns the heads' outputs side by side,
    [B, T, N * Dv]."""
    b, t = q[0].shape[:2]
    out = flash_span_attention(
        tuple(part.transpose(0, 2, 1, 3) for part in q), k, v, q_pos[:, 0],
        scale=scale, block=block, interpret=jax.default_backend() != "tpu",
        **({"window": window} if window else {}))
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "scale"),
)
def _flash_backward(q, k, v, o, lse, do, *, causal: bool,
                    scale: float | None, block_q: int | None,
                    block_k: int | None, interpret: bool):
    """(dq, dk, dv) from the forward's output and log-sum-exp."""
    b, n, tq, d = q.shape
    tk = k.shape[2]
    scale, bq, bk, params = _flash_plan(q, k, scale, block_q, block_k)
    nq, nk = tq // bq, tk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(b * n, 1, tq)
    operands = (q.reshape(b * n, tq, d), k.reshape(b * n, tk, d),
                v.reshape(b * n, tk, d), do.reshape(b * n, tq, d), lse, delta)
    kernel = dict(scale=scale, causal=causal, block_q=bq, block_k=bk)

    q_spec, kv_spec, row_spec = _flash_specs(bq, bk, d, causal, nk,
                                             q_inner=True)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **kernel),
        name="fm_flash_bwd_dkv",
        grid=(b * n, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b * n, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * n, tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
    )(*operands)

    q_spec, kv_spec, row_spec = _flash_specs(bq, bk, d, causal, nk,
                                             q_inner=False)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **kernel),
        name="fm_flash_bwd_dq",
        grid=(b * n, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * n, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(*operands)
    return (dq.reshape(b, n, tq, d), dk.reshape(b, n, tk, d),
            dv.reshape(b, n, tk, d))
