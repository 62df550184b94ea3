"""Attention kernels: Pallas flash attention + XLA reference.

The reference has no attention anywhere (SURVEY §2.6) — sequence length
only sizes its token batch.  A complete framework needs the full model, and
long-context support is first-class here: this module provides the
single-chip blockwise (flash) attention kernel whose online-softmax
accumulator is also the building block of the ring attention in
:mod:`flashmoe_tpu.parallel.ringattn` (same math, kv blocks arriving over
ICI instead of from HBM).

Layouts: q/k/v are [B, N, T, D] (batch, heads, time, head_dim); GQA is
handled by the caller repeating kv heads (cheap view under XLA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
# Flash attention kernel
# ----------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, block_q, block_k):
    """Grid: (B*N, Tq/block_q, Tk/block_k) — kv innermost, accumulating the
    online softmax in VMEM scratch."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip fully-masked kv blocks (strictly above the diagonal); m/l scratch
    # is lane-width (bq, 128) holding broadcast copies to keep TPU layouts
    # happy, like the upstream flash kernels
    run = (
        k_start <= q_start + block_q - 1 if causal else jnp.bool_(True)
    )

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_start
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + k_start
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[:, :1]                   # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)         # [bq, 1]
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = (
            acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Blockwise attention. q/k/v: [B, N, T, D] with T % block == 0.

    Differentiable: the forward is the Pallas kernel; the backward
    recomputes through :func:`attention_xla` (the kernel writes no
    residuals, and ``pallas_call`` itself has no transpose rule — on the
    chip ``jax.grad`` through the bare kernel dies in its JVP rule, which
    is how the trainer first met it)."""
    return _flash_ad(q, k, v, causal, scale, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_ad(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)


def _flash_ad_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)
    return out, (q, k, v)


def _flash_ad_bwd(causal, scale, block_q, block_k, interpret, res, dy):
    _, vjp = jax.vjp(
        lambda q, k, v: attention_xla(q, k, v, causal=causal, scale=scale),
        *res)
    return vjp(dy)


_flash_ad.defvjp(_flash_ad_fwd, _flash_ad_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "scale"),
)
def _flash_forward(q, k, v, *, causal: bool, scale: float | None,
                   block_q: int, block_k: int, interpret: bool):
    b, n, tq, d = q.shape
    tk = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    if tq % bq or tk % bk:
        raise ValueError(f"T ({tq},{tk}) must divide blocks ({bq},{bk})")

    qf = q.reshape(b * n, tq, d)
    kf = k.reshape(b * n, tk, d)
    vf = v.reshape(b * n, tk, d)
    grid = (b * n, tq // bq, tk // bk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk,
        ),
        name="fm_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * n, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, n, tq, d)
