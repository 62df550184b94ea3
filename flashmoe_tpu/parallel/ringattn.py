"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has no attention and no sequence parallelism (SURVEY §2.6 —
its only ring algorithm is an intra-GPU block-ring over the *expert*
dimension in the gate).  Long context is first-class in this framework, so
this module implements ring attention (Liu et al.) the TPU way: each sp
rank holds a sequence shard of q/k/v; kv shards rotate around the ring via
``jax.lax.ppermute`` (XLA lowers this to ICI neighbour transfers), and each
rank folds every arriving kv block into its queries' online-softmax
accumulator (the same (m, l, acc) recursion as the flash kernel in
:mod:`flashmoe_tpu.ops.attention`).  XLA overlaps the next ppermute with
the current block's compute automatically (async collective + latency-
hiding scheduler).

Causal masking works on global positions: rank r's queries start at
``r * T_loc``; the kv shard arriving at step s originated at rank
``(r - s) mod D``.  Blocks wholly above the diagonal are skipped via a
zero contribution (static control flow, no dynamic shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flashmoe_tpu.ops.attention import NEG_INF


def _block_attn(q, k, v, q_off, kv_off, scale, causal):
    """One (q-shard, kv-shard) partial: returns (m, l, o_unnormalized)."""
    s = jnp.einsum(
        "bntd,bnsd->bnts", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        qi = jnp.arange(tq)[:, None] + q_off
        ki = jnp.arange(tk)[None, :] + kv_off
        s = jnp.where((qi >= ki)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B, N, Tq, 1]
    # fully-masked rows: exp(NEG_INF - NEG_INF) would give 1s; clamp m
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= NEG_INF, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum(
        "bnts,bnsd->bntd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_safe, l, o


def _ring_shard(q, k, v, *, axis, scale, causal):
    """Per-rank body. q/k/v: [B, N, T_loc, D] local shards."""
    d_world = jax.lax.axis_size(axis)
    my = jax.lax.axis_index(axis)
    t_loc = q.shape[2]
    q_off = my * t_loc

    m_run = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    l_run = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)

    def step(s, carry):
        m_run, l_run, acc, k_cur, v_cur = carry
        src = jax.lax.rem(my - s + d_world, d_world)
        kv_off = src * t_loc
        m_blk, l_blk, o_blk = _block_attn(
            q, k_cur, v_cur, q_off, kv_off, scale, causal
        )
        m_new = jnp.maximum(m_run, m_blk)
        a_run = jnp.exp(m_run - m_new)
        a_blk = jnp.exp(m_blk - m_new)
        l_new = l_run * a_run + l_blk * a_blk
        acc_new = acc * a_run + o_blk * a_blk
        # rotate kv to the next rank (ring: receive from my-1 direction)
        perm = [(i, (i + 1) % d_world) for i in range(d_world)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return m_new, l_new, acc_new, k_nxt, v_nxt

    # static unroll over ring steps (D is a mesh constant) so XLA can
    # overlap each step's ppermute with the next block's compute
    carry = (m_run, l_run, acc, k, v)
    for s in range(d_world):
        carry = step(s, carry)
    m_run, l_run, acc, _, _ = carry
    return (acc / jnp.maximum(l_run, 1e-30)).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                   causal: bool = True, scale: float | None = None):
    """Ring attention over the sequence axis.

    q/k/v: [B, N, T, D] global; T shards over ``axis``.  Returns [B, N, T, D].
    """
    dd = q.shape[-1]
    scale = scale if scale is not None else dd ** -0.5
    body = functools.partial(_ring_shard, axis=axis, scale=scale,
                             causal=causal)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
        check_vma=False,
    )
    return fn(q, k, v)
