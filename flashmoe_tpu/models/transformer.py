"""FlashMoE-TPU transformer: the flagship MoE model family.

The reference is a kernel library, not a model — its Python worker feeds
random tensors through one MoE layer (``flashmoe/worker.py:56-67``), and the
full-model dimensions (num_layers, moe_frequency, vocab_size) exist only to
feed the Decider's cost model.  A complete framework needs the model around
the layer, so this module provides a modern MoE transformer (pre-norm,
RoPE, GQA attention, MoE FFN every ``moe_frequency``-th layer, optional
shared experts) in functional JAX style:

  * params are plain nested dicts (pytree), shardable with the
    PartitionSpecs from :mod:`flashmoe_tpu.parallel.mesh`;
  * :func:`forward` is jit-friendly (static config, no Python-level data
    dependence), uses the fused MoE layer per token shard;
  * :func:`loss_fn` / :func:`train_step` give the full training path
    (cross-entropy + load-balance aux + z-loss, optax-compatible grads)
    — the capability the reference models in its Decider (DP gradient
    allreduce pricing, ``os/decider/functions.cuh:28-32``) but never
    executes;
  * rematerialization via ``jax.checkpoint`` per block keeps HBM bounded.

Layer geometry follows cfg.moe_layer_indices (moe_frequency), mirroring the
reference's ``moe_frequency`` semantics.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import FFN_PARTS, MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops.attention import MIXER_SPANS
from flashmoe_tpu.ops.attention import rms_norm  # noqa: F401  (re-exported)
from flashmoe_tpu.ops.attention import rope_halves as _rope  # noqa: F401
from flashmoe_tpu.ops.moe import dense_ffn, moe_layer
from flashmoe_tpu.parallel.ep import ep_moe_layer
from flashmoe_tpu.utils.telemetry import trace_span


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------

def init_params(key, cfg: MoEConfig) -> dict:
    """Initialize the full transformer parameter tree."""
    h = cfg.hidden_size
    nh, nkv, dh = cfg.num_heads, cfg.resolved_num_kv_heads, cfg.resolved_head_dim
    keys = jax.random.split(key, cfg.num_layers + 3)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, cfg.param_dtype) / jnp.sqrt(fan_in)

    params: dict[str, Any] = {
        "embed": dense(keys[0], (cfg.vocab_size, h), 1.0) * 0.02 * jnp.sqrt(1.0),
        "final_norm": jnp.ones((h,), cfg.param_dtype),
        "layers": [],
    }
    if not cfg.tie_embeddings:      # tied: the head IS ``embed``, one leaf
        params["lm_head"] = dense(keys[1], (h, cfg.vocab_size), h)
    for li, (mixer, ffn) in enumerate(cfg.layers):
        lk = jax.random.split(keys[2 + li], 6)
        # one norm for each part the layer has (``cfg.layers``)
        layer = {name: jnp.ones((h,), cfg.param_dtype)
                 for name, part in (("attn_norm", mixer), ("ffn_norm", ffn))
                 if part is not None}
        if mixer is None:
            pass
        elif mixer == "kda":
            ak = jax.random.split(lk[0], 7)
            n, d = cfg.kda_heads, cfg.kda_head_dim
            layer.update(
                kda_wqkv=dense(ak[0], (h, 3 * n * d), h),
                kda_conv=dense(ak[1], (cfg.kda_conv, 3 * n * d),
                               cfg.kda_conv),
                kda_wa=dense(ak[2], (h, n * d), h),
                # the decay's per-head rate and per-channel offset are
                # not matrices: float32 whatever the weights' dtype
                kda_A=jnp.zeros((n,), jnp.float32),
                kda_b=jax.random.normal(ak[3], (n * d,), jnp.float32),
                kda_wb=dense(ak[4], (h, n), h),
                kda_wg=dense(ak[5], (h, n), h),
                kda_norm=jnp.ones((d,), cfg.param_dtype),
                wo=dense(ak[6], (n * d, h), n * d))
        elif mixer == "conv":
            ak = jax.random.split(lk[0], 3)
            layer.update(
                conv_win=dense(ak[0], (h, 3 * h), h),
                conv_w=dense(ak[1], (cfg.conv_taps, h), cfg.conv_taps),
                wo=dense(ak[2], (h, h), h))
        elif mixer == "ssm":
            ak = jax.random.split(lk[0], 5)
            n, di, width = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width
            layer.update(
                ssm_win=dense(ak[0], (h, di + width + n), h),
                ssm_conv_w=dense(ak[1], (cfg.ssm_conv, width),
                                 cfg.ssm_conv),
                ssm_conv_b=jnp.zeros((width,), cfg.param_dtype),
                # the step's bias, the decay's rate and the skip are not
                # matrices: float32 whatever the weights' dtype (steps
                # from softplus^-1 of 0.001 .. 0.1, rates A = -1 .. -16)
                ssm_dt_bias=jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                    ak[2], (n,), jnp.float32, jnp.log(1e-3),
                    jnp.log(1e-1))))),
                ssm_A_log=jnp.log(jax.random.uniform(
                    ak[3], (n,), jnp.float32, 1.0, 16.0)),
                ssm_D=jnp.ones((n,), jnp.float32),
                ssm_norm=jnp.ones((di,), cfg.param_dtype),
                wo=dense(ak[4], (di, h), di))
        elif cfg.attention_kind == "mla":
            ak = jax.random.split(lk[0], 4)
            rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
            dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            if rq:
                layer.update(
                    wq_a=dense(ak[0], (h, rq), h),
                    q_a_norm=jnp.ones((rq,), cfg.param_dtype),
                    wq_b=dense(ak[1], (rq, nh * (dn + dr)), rq))
            else:
                layer.update(wq=dense(ak[0], (h, nh * (dn + dr)), h))
            layer.update(
                wkv_a=dense(ak[2], (h, rkv + dr), h),
                kv_a_norm=jnp.ones((rkv,), cfg.param_dtype),
                wkv_b=dense(ak[3], (rkv, nh * (dn + dv)), rkv),
                wo=dense(lk[3], (nh * dv, h), nh * dv))
        else:
            layer.update(
                wq=dense(lk[0], (h, nh * dh), h),
                wk=dense(lk[1], (h, nkv * dh), h),
                wv=dense(lk[2], (h, nkv * dh), h),
                wo=dense(lk[3], (nh * dh, h), nh * dh))
            if cfg.qk_norm:
                layer.update(q_norm=jnp.ones((dh,), cfg.param_dtype),
                             k_norm=jnp.ones((dh,), cfg.param_dtype))
            if cfg.attn_gate:
                layer.update(wg=dense(lk[5], (h, nh * dh), h))
        if cfg.part_out_norm:
            # a norm on each part's OUTPUT, beside the one on its input
            layer.update({
                name: jnp.ones((h,), cfg.param_dtype)
                for name, part in (("attn_out_norm", mixer),
                                   ("ffn_out_norm", ffn))
                if part is not None})
        if ffn is not None:
            layer["moe"] = init_moe_params(lk[4], cfg.ffn_config(li))
        if FFN_PARTS[ffn][1] == "moe":
            # the mixture read beside the dense part, joined later
            layer["branch"] = init_moe_params(
                lk[5], cfg.ffn_config(li, branch=True))
        params["layers"].append(layer)
    return params


# ----------------------------------------------------------------------
# Where the stream starts, grows and ends: the ONE place of each of the
# config's scalar factors (``MoEConfig.embedding_multiplier`` ...)
# ----------------------------------------------------------------------

def embed_tokens(params, cfg: MoEConfig, tokens):
    """Rows of the embedding, scaled: tokens [...] int32 -> [..., H] in
    the activations' dtype, times ``cfg.embedding_multiplier``."""
    with trace_span("lm.embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    return x


def join_stream(cfg: MoEConfig, x, part):
    """A part's output joins the residual stream:
    ``x + cfg.residual_multiplier * part``.  (A part's OUTPUT norm,
    ``cfg.part_out_norm``, is its callers': :func:`part_out`.)"""
    if cfg.residual_multiplier != 1.0:
        part = part * cfg.residual_multiplier
    return x + part


def part_out(cfg: MoEConfig, layer, name: str, part):
    """A part's output as it joins: through the layer's norm ``name``
    (``attn_out_norm`` / ``ffn_out_norm``) under ``cfg.part_out_norm``, as
    it is otherwise."""
    if cfg.part_out_norm:
        part = rms_norm(part, layer[name], cfg.norm_eps)
    return part


def head_logits(params, cfg: MoEConfig, x):
    """The final norm and the head over hidden states [..., H] ->
    float32 logits [..., V], divided by ``cfg.logits_scaling``.  A tied
    head (``cfg.tie_embeddings``) contracts over the embedding's own
    ``[V, H]`` array: no transposed copy of it anywhere."""
    with trace_span("lm.head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "...h,vh->...v", x, params["embed"].astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        else:
            logits = jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                             preferred_element_type=jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
    return logits


# ----------------------------------------------------------------------
# Blocks
# ----------------------------------------------------------------------

def attention(layer, x, cfg: MoEConfig, positions=None, mesh=None,
              use_pallas=None, li: int = 0):
    """Layer ``li``'s token mixer over a whole sequence (no cache): causal
    self-attention with RoPE and GQA, latent attention, the delta rule in
    its chunkwise form, the gated short convolution or the state-space
    mixer in its chunked form, by ``cfg.mixers[li]``.  x: [B, T, H].

    Backend selection of the K/V kind: ring attention over the ``sp``
    mesh axis for sequence-parallel configs, the flash Pallas kernel on
    TPU, plain XLA otherwise.
    """
    from flashmoe_tpu.config import STATE_MIXERS
    from flashmoe_tpu.ops.attention import (
        attention_xla, flash_attention, kv_paged_attention, kv_project,
        mla_paged_attention, paged_attention,
    )
    from flashmoe_tpu.parallel.ringattn import ring_attention

    b, t, h = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    if cfg.mixers[li] in STATE_MIXERS:
        if mesh is not None and cfg.sp > 1:
            raise NotImplementedError(
                f"a {cfg.mixers[li]!r} layer under sp > 1: the state would "
                f"have to pass from one sequence shard to the next")
        return paged_attention(layer, x, cfg, None, li, positions, None,
                               None, absorbed=False)[0]
    if cfg.attention_kind == "mla":
        # plain XLA, the first (decompressing) form: flash_attention
        # assumes equal q/k/v head sizes, ring attention K/V shards
        if mesh is not None and cfg.sp > 1:
            raise NotImplementedError(
                "attention_kind='mla' under sp > 1: ring attention "
                "passes K/V shards; a latent-row ring is missing")
        return mla_paged_attention(layer, x, cfg, None, 0, positions,
                                   None, None, absorbed=False)[0]
    nh, nkv, dh = cfg.num_heads, cfg.resolved_num_kv_heads, cfg.resolved_head_dim
    if cfg.windowed:
        # a window, a rotation by layer kind, an output gate: the cached
        # paths' form over the sequence as its own context (serving only)
        return kv_paged_attention(layer, x, cfg, None, 0, positions, None,
                                  None, cfg.mixers[li])[0]

    q, k, v = kv_project(layer, x, cfg, positions)

    if nkv != nh:  # GQA: repeat kv heads
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    # [B, T, N, D] -> [B, N, T, D] for the attention kernels
    qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    scale = cfg.attention_multiplier            # None: head_dim ** -0.5
    if mesh is not None and cfg.sp > 1:
        ctx = ring_attention(qh, kh, vh, mesh, causal=True, scale=scale)
    elif use_pallas and t % 128 == 0:
        ctx = flash_attention(qh, kh, vh, causal=True, scale=scale)
    else:
        ctx = attention_xla(qh, kh, vh, causal=True, scale=scale)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, nh * dh).astype(x.dtype)
    return ctx @ layer["wo"].astype(x.dtype)


def _resolved_plan(cfg: MoEConfig, mesh) -> tuple[str, int | None]:
    """(moe_backend, a2a_chunks) with 'auto' resolved by the analytical
    planner (predicted-latency winner + chunked-pipeline sweep,
    measured override; decision recorded in telemetry).  The pricing
    regime follows ``cfg.serving_mode``: a decode-phase config
    (``serving_mode='decode'``, set by the serving engine) resolves a
    decode-priced plan — per-step tokens = the decode batch, not
    B x S — instead of the training-shaped sweep."""
    if cfg.moe_backend != "auto":
        return cfg.moe_backend, cfg.a2a_chunks
    from flashmoe_tpu.parallel.ep import resolve_moe_plan

    return resolve_moe_plan(cfg, mesh)


def _ffn(layer, x, cfg: MoEConfig, li: int, mesh, use_pallas,
         branch: bool = False):
    """FFN sub-block: MoE (possibly expert-parallel) or dense; with
    ``branch`` the mixture branch read at this layer."""
    b, t, h = x.shape
    flat = x.reshape(b * t, h)
    layer_cfg = cfg.ffn_config(li, branch)
    ffn_params = layer["branch" if branch else "moe"]
    if mesh is not None and layer_cfg.num_experts > 1 and cfg.ep > 1:
        axes = ("dp", "ep") + (("sp",) if cfg.sp > 1 else ())
        backend, chunks = _resolved_plan(cfg, mesh)
        # the planner's chunked-pipeline pick rides the layer config
        # (parallel/ep.py reads cfg.a2a_chunks); explicit settings and
        # unservable picks pass through untouched
        from flashmoe_tpu.parallel.ep import apply_chunk_pick

        layer_cfg = apply_chunk_pick(layer_cfg, backend, chunks)
        if backend == "fused" and cfg.tp == 1:
            from flashmoe_tpu.parallel.fused import fused_ep_moe_layer

            # distinct collective_id per layer: each fused kernel in the
            # step needs its own barrier-semaphore identity
            # the fused layer IS a Pallas kernel — interpret it anywhere
            # but on real TPU, independent of the use_pallas preference
            o = fused_ep_moe_layer(ffn_params, flat, layer_cfg, mesh,
                                   token_axes=axes,
                                   collective_id=7 + (li % 16),
                                   interpret=jax.default_backend() != "tpu")
        elif (backend == "ragged" and cfg.tp == 1
                and not layer_cfg.num_shared_experts):
            from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

            o = ragged_ep_moe_layer(ffn_params, flat, layer_cfg, mesh,
                                    use_pallas=bool(use_pallas),
                                    interpret=bool(use_pallas)
                                    and jax.default_backend() != "tpu",
                                    token_axes=axes)
        else:
            o = ep_moe_layer(ffn_params, flat, layer_cfg, mesh,
                             use_pallas=bool(use_pallas),
                             token_axes=axes)
    elif layer_cfg.zero_experts or layer_cfg.experts_held:
        # what only the routed rows compute (ops/moe.routed_rows_ffn)
        o = moe_layer(ffn_params, flat, layer_cfg, use_pallas=False,
                      routed_rows=True)
    else:
        o = moe_layer(ffn_params, flat, layer_cfg, use_pallas=use_pallas)
    return (o.out.reshape(b, t, h).astype(x.dtype),
            o.aux_loss + o.z_loss, o.stats)


def block(layer, x, cfg: MoEConfig, li: int, mesh=None, use_pallas=None,
          chaos_sig=(), carried=None):
    """One pre-norm transformer block: the parts ``cfg.layers[li]`` names
    (a mixer, a feed-forward part, or both), each behind its own norm.
    Returns (x, moe_losses, moe_stats, carried) — stats is the layer's
    MoEStats when ``cfg.collect_stats`` and this is an MoE layer, else
    None (an empty pytree leaf); ``carried`` is the output of a mixture
    branch that is open across this layer (``MoEConfig.layer_ffns``: read
    here or before, it joins after a later layer's feed-forward part),
    None where none is.

    ``chaos_sig`` is the chaos-injection registry snapshot
    (:func:`flashmoe_tpu.chaos.inject.trace_signature`), unused in the
    body but STATIC: ``jax.checkpoint`` caches block traces by
    (function, static args), and without the signature in the key a
    re-armed injection point silently reuses the previous arming
    state's jaxpr whenever two builds share an equal config (the chaos
    drills rebuild their step exactly to pick up new arming)."""
    mixer, ffn = cfg.layers[li]
    if mixer is not None:
        scope = MIXER_SPANS[mixer]
        with trace_span(scope):  # staticcheck: ok a MIXER_SPANS name
            a = attention(
                layer, rms_norm(x, layer["attn_norm"], cfg.norm_eps), cfg,
                mesh=mesh, use_pallas=use_pallas, li=li)
            x = join_stream(cfg, x, part_out(cfg, layer, "attn_out_norm", a))
    if ffn is None:
        return x, jnp.zeros((), cfg.accum_dtype), None, carried
    part, branch = FFN_PARTS[ffn]
    with trace_span("ffn.moe") if part == "moe" else trace_span("ffn.dense"):
        f_in = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        f, moe_loss, moe_stats = _ffn(layer, f_in, cfg, li, mesh,
                                      use_pallas)
        x = join_stream(cfg, x, part_out(cfg, layer, "ffn_out_norm", f))
    if branch == "moe":
        with trace_span("ffn.moe"):
            carried, branch_loss, moe_stats = _ffn(
                layer, f_in, cfg, li, mesh, use_pallas, branch=True)
        moe_loss = moe_loss + branch_loss
    elif branch == "join":
        x, carried = join_stream(cfg, x, carried), None
    return x, moe_loss, moe_stats, carried


# ----------------------------------------------------------------------
# Model forward / loss / train step
# ----------------------------------------------------------------------

def forward(params, tokens, cfg: MoEConfig, mesh=None, use_pallas=None):
    """tokens: [B, T] int32 -> logits [B, T, V]; also returns summed MoE
    aux losses.  With ``cfg.collect_stats`` a third element is returned:
    a tuple of per-MoE-layer :class:`flashmoe_tpu.ops.stats.MoEStats`
    (flag off keeps the two-tuple contract every existing caller uses)."""
    x = embed_tokens(params, cfg, tokens)
    total_aux = jnp.zeros((), cfg.accum_dtype)
    layer_stats = []
    # per-block remat keeps HBM bounded; excluded exactly for the blocks
    # where the fused RDMA backend actually runs (same condition as _ffn's
    # fused branch — its kernel's side effects cannot be partially
    # evaluated under checkpoint, and its custom VJP already avoids
    # storing the exchange intermediates).  Non-MoE blocks keep remat.
    fused_active = (cfg.ep > 1 and cfg.tp == 1 and mesh is not None
                    and cfg.num_experts > 1
                    and _resolved_plan(cfg, mesh)[0] == "fused")
    blk_remat = jax.checkpoint(
        block, static_argnums=(2, 3, 4, 5, 6),
        policy=jax.checkpoint_policies.nothing_saveable,
    )
    from flashmoe_tpu.chaos import inject as chaos_inject

    chaos_sig = chaos_inject.trace_signature()
    moe_layers = set(cfg.moe_layer_indices)
    carried = None
    for li, layer in enumerate(params["layers"]):
        fused_block = fused_active and li in moe_layers
        blk = blk_remat if (cfg.is_training and not fused_block) else block
        x, moe_loss, moe_stats, carried = blk(
            layer, x, cfg, li, mesh, use_pallas, chaos_sig, carried)
        total_aux = total_aux + moe_loss
        if moe_stats is not None:
            layer_stats.append(moe_stats)
    logits = head_logits(params, cfg, x)
    if cfg.collect_stats:
        return logits, total_aux, tuple(layer_stats)
    return logits, total_aux


def loss_fn(params, batch, cfg: MoEConfig, mesh=None, use_pallas=None):
    """Next-token cross-entropy + MoE aux losses.

    batch: dict with "tokens" [B, T] (inputs are tokens[:, :-1], targets
    tokens[:, 1:]).
    """
    tokens = batch["tokens"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    if cfg.collect_stats:
        logits, aux, stats = forward(params, inp, cfg, mesh, use_pallas)
    else:
        logits, aux = forward(params, inp, cfg, mesh, use_pallas)
        stats = ()
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(tgt, jnp.float32))
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    metrics = {"ce": ce, "aux": aux}
    if cfg.collect_stats:
        # per-MoE-layer MoEStats, consumed by the trainer's flight
        # recorder; stays a pytree of arrays so it flows through jit
        metrics["moe_stats"] = stats
    return ce + aux, metrics


def sgd_train_step(params, batch, cfg: MoEConfig, lr=1e-3, mesh=None,
                   use_pallas=None):
    """Minimal fused train step (plain SGD) — used by the multi-chip
    dry-run; the full optimizer path lives in
    :mod:`flashmoe_tpu.runtime.trainer`."""
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg, mesh, use_pallas
    )
    params = jax.tree_util.tree_map(
        lambda p, g: (p - lr * g.astype(p.dtype)).astype(p.dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params, grads,
    )
    return params, loss, metrics
