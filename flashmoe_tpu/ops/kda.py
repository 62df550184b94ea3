"""Kimi delta attention (KDA): the gated delta rule with a per-channel
decay, in plain XLA, in its two forms behind ONE function.

A 'kda' layer keeps, whatever the context's length, a float32 state
``S [d_k, d_v]`` a head and the last ``kda_conv - 1`` inputs of its
convolution; nothing a token, no positions.  x is the normed input of the
block, n indexes the heads, eps 1e-6:

    [q~ | k~ | v~] = x W_qkv; each channel through a causal depthwise
    convolution of kda_conv taps over time, then SiLU
    q_n = l2norm(q'_n), k_n = l2norm(k'_n)
    log alpha_t = L * sigmoid(exp(A_n) * (x W_a + b))   in (L, 0), a CHANNEL
    beta_t = sigmoid(x W_b), g_t = x W_g                one a head
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        = D + k_t u_t^T,  D = Diag(alpha_t) S_{t-1},
          u_t = beta_t (v_t - D^T k_t)
    o_t = d_k^-1/2 S_t^T q_t;   out = [RMSNorm(o_t) * sigmoid(g_t)]_n W_o

:func:`kda_step` is the recurrence, one token (decode).  :func:`kda_chunked`
is the same numbers a chunk of C tokens at a time (prefill, chunked
prefill): with G_t the running sum of log alpha inside the chunk,

    A_ti = sum_c k_tc k_ic exp(G_tc - G_ic)  (i < t),   B_ti the same with
    q_t (i <= t),   (I + Diag(beta) A) [U0 | W] = Diag(beta) [V | K exp(G)]
    per chunk, all chunks at once; then a scan over the chunks:
    U = U0 - W S,  O = d_k^-1/2 ((Q exp(G)) S + B U),
    S <- Diag(exp(G_C)) S + (K exp(G_C - G))^T U.

The decays enter A and B only as exp of DIFFERENCES of G.  Taken against
the running sum at the start of the row's 16-token sub-chunk, every
exponent is <= 0 except inside the diagonal sub-blocks, where it is at
most 16 x |L| = 80 < log(float32 max) = 88.7: what the published lower
bound L = -5 buys.  A position that is not ``valid`` has log alpha = 0 and
beta = 0 and leaves the state as it was.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flashmoe_tpu.utils.telemetry import trace_span

#: tokens a chunk of the chunkwise form holds, and a sub-chunk of it
CHUNK, SUB = 64, 16

_HI = dict(precision=jax.lax.Precision.HIGHEST,
           preferred_element_type=jnp.float32)


def _l2norm(x, eps=1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_project(layer, x, cfg, conv, valid):
    """x: [B, T, H] normed; conv: [B, K - 1, 3 N D] the convolution's last
    inputs (None: zeros); valid: [B, T] bool, a PREFIX of each row (None:
    all).  Returns q, k, v [B, T, N, D] float32 (q, k of unit length),
    log_alpha [B, T, N, D] and beta [B, T, N] float32 (0 where not valid),
    the output gate [B, T, N] and the convolution's inputs after the
    row's last valid position [B, K - 1, 3 N D]."""
    b, t, _ = x.shape
    n, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    u = x @ layer["kda_wqkv"].astype(x.dtype)                # [B, T, 3ND]
    if conv is None:
        conv = jnp.zeros((b, taps - 1, u.shape[-1]), u.dtype)
    full = jnp.concatenate([conv.astype(u.dtype), u], axis=1)
    w = layer["kda_conv"].astype(jnp.float32)                # [K, 3ND]
    y = sum(full[:, j:j + t].astype(jnp.float32) * w[j]
            for j in range(taps))
    q, k, v = jnp.split(jax.nn.silu(y).reshape(b, t, 3 * n, d), 3, axis=2)
    n_valid = (jnp.full((b,), t, jnp.int32) if valid is None
               else jnp.sum(valid, axis=1, dtype=jnp.int32))
    conv = jnp.take_along_axis(
        full, (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    proj = lambda w: jnp.dot(x, layer[w].astype(x.dtype),
                             preferred_element_type=jnp.float32)
    a = (proj("kda_wa") + layer["kda_b"].astype(jnp.float32)).reshape(
        b, t, n, d)
    log_alpha = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(layer["kda_A"].astype(jnp.float32))[:, None] * a)
    beta, gate = jax.nn.sigmoid(proj("kda_wb")), proj("kda_wg")
    if valid is not None:
        log_alpha = jnp.where(valid[:, :, None, None], log_alpha, 0.0)
        beta = jnp.where(valid[:, :, None], beta, 0.0)
    return _l2norm(q), _l2norm(k), v, log_alpha, beta, gate, conv


def kda_step(q, k, v, log_alpha, beta, state):
    """The recurrence, one token.  q, k, v, log_alpha: [B, N, D] float32;
    beta: [B, N]; state: [B, N, D, D] float32.  Returns (o [B, N, D], the
    new state).  Multiplies and sums, no matrix unit: float32 throughout,
    and one pass over the decayed state gives both products with it."""
    s = state * jnp.exp(log_alpha)[..., None]
    s_k = jnp.sum(s * k[..., None], axis=-2)                 # D^T k
    s_q = jnp.sum(s * q[..., None], axis=-2)                 # D^T q
    u = beta[..., None] * (v - s_k)
    o = s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o * q.shape[-1] ** -0.5, s + k[..., None] * u[..., None, :]


def _intra(q, k, g):
    """A (strictly lower) and B (lower) of every chunk.  q, k, g:
    [..., C, D], g the running log decay.  Row sub-chunk j is taken
    against R_j, the running sum before its first token: rows carry
    exp(G_t - R_j) <= 1, columns exp(R_j - G_i), masked to the columns of
    sub-chunks <= j (beyond them the exponent grows without bound)."""
    c, d = q.shape[-2:]
    ns = c // SUB
    lead = q.shape[:-2]
    sub = lambda x: x.reshape(*lead, ns, SUB, d)
    ref = jnp.concatenate(
        [jnp.zeros((*lead, 1, d), g.dtype), sub(g)[..., :-1, -1, :]],
        axis=-2)                                             # [..., ns, D]
    row = jnp.exp(sub(g) - ref[..., :, None, :])             # [.., ns, SUB, D]
    seen = (jnp.arange(c)[None, :] // SUB
            <= jnp.arange(ns)[:, None])[..., None]           # [ns, C, 1]
    col = k[..., None, :, :] * jnp.exp(jnp.where(
        seen, ref[..., :, None, :] - g[..., None, :, :], -jnp.inf))
    both = jnp.stack([sub(k), sub(q)], axis=-4) * row[..., None, :, :, :]
    ab = jnp.einsum("...jtd,...jid->...jti", both, col[..., None, :, :, :],
                    **_HI).reshape(*lead, 2, c, c)
    t, i = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    return (jnp.where(t > i, ab[..., 0, :, :], 0.0),
            jnp.where(t >= i, ab[..., 1, :, :], 0.0))


def kda_chunked(q, k, v, log_alpha, beta, state):
    """The same numbers as :func:`kda_step` token by token, a chunk at a
    time.  q, k, v, log_alpha: [B, T, N, D] float32; beta: [B, T, N];
    state: [B, N, D, D] float32.  Returns (o [B, T, N, D], the state
    after the span).  T is padded to whole chunks with positions that
    leave the state alone."""
    b, t, n, d = q.shape
    c = CHUNK if t >= CHUNK else -(-t // SUB) * SUB
    pad = -t % c
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, log_alpha, beta))
    nc = (t + pad) // c
    # [B, T, N, D] -> [nc, B, N, C, D]: the scan runs over the chunks
    chunks = lambda x: x.reshape(b, nc, c, n, -1).transpose(1, 0, 3, 2, 4)
    q, k, v, la, beta = map(chunks, (q, k, v, log_alpha, beta))
    g = jnp.cumsum(la, axis=-2)
    a_mat, b_mat = _intra(q, k, g)
    decay = jnp.exp(g)
    rhs = beta * jnp.concatenate([v, k * decay], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        jnp.eye(c, dtype=jnp.float32) + beta * a_mat, rhs, lower=True,
        unit_diagonal=True)
    g_end = g[..., -1:, :]
    xs = (sol[..., :d], sol[..., d:], q * decay, b_mat,
          k * jnp.exp(g_end - g), jnp.exp(g_end[..., 0, :]))

    def chunk(s, x):
        u0, w, q_dec, b_m, k_end, dec_end = x
        u = u0 - jnp.einsum("bnck,bnkv->bncv", w, s, **_HI)
        o = (jnp.einsum("bnck,bnkv->bncv", q_dec, s, **_HI)
             + jnp.einsum("bnci,bniv->bncv", b_m, u, **_HI))
        s = dec_end[..., None] * s + jnp.einsum(
            "bnck,bncv->bnkv", k_end, u, **_HI)
        return s, o

    state, o = jax.lax.scan(chunk, state, xs)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, nc * c, n, d)[:, :t]
    return o * d ** -0.5, state


def kda_attention(layer, x, cfg, state, conv, si, valid=None, slots=None,
                  fresh=None):
    """THE 'kda' mixer of every cached path: project a span of T tokens a
    row, run the recurrence from the rows' state (one step for T = 1, the
    chunkwise form for a longer span), write the state back.

    x: [B, T, H] normed; state: [L_s, S, N, D, D] float32 and conv:
    [L_s, S, (K - 1) * 3 N D] (a slot's K - 1 inputs side by side: a
    3-row axis would be padded to a whole tile), the per-SLOT state of
    every 'kda' layer, or None for a whole prompt at once (it starts from
    nothing); si: this
    layer's index among them; valid: [B, T] bool, a prefix of each row
    (None: all); slots: [B] the slot each row owns (None: row b owns slot
    b); fresh: scalar bool, the rows start from nothing whatever the
    slots hold (a prompt's first chunk).  Returns (the block's output
    [B, T, H], state, conv, the rows' state after the span
    ``(s [B, N, D, D], conv [B, (K - 1) * 3 N D])``)."""
    b, t, _ = x.shape
    n, d = cfg.kda_heads, cfg.kda_head_dim
    if state is None:
        s0 = c0 = None
    else:
        s0, c0 = ((state[si], conv[si]) if slots is None
                  else (state[si, slots], conv[si, slots]))
        if fresh is not None:
            s0 = jnp.where(fresh, 0.0, s0)
            c0 = jnp.where(fresh, jnp.zeros((), c0.dtype), c0)
        c0 = c0.reshape(b, cfg.kda_conv - 1, -1)
    q, k, v, log_alpha, beta, gate, c1 = kda_project(layer, x, cfg, c0,
                                                     valid)
    c1 = c1.reshape(b, -1)
    if s0 is None:
        s0 = jnp.zeros((b, n, d, d), jnp.float32)
    if t == 1:
        with trace_span("attn.kda_decode"):
            o, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0],
                             beta[:, 0], s0)
            o = o[:, None]
    else:
        with trace_span("attn.kda_prefill"):
            o, s1 = kda_chunked(q, k, v, log_alpha, beta, s0)
    if valid is not None and state is not None:
        # a row with nothing valid keeps its state to the bit
        live = jnp.any(valid, axis=1)
        s1 = jnp.where(live[:, None, None, None], s1, s0)
    if state is not None:
        if slots is None:
            state, conv = state.at[si].set(s1), conv.at[si].set(
                c1.astype(conv.dtype))
        else:
            state = state.at[si, slots].set(s1)
            conv = conv.at[si, slots].set(c1.astype(conv.dtype))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-6)
    o = (o * layer["kda_norm"].astype(jnp.float32)
         * jax.nn.sigmoid(gate)[..., None])
    out = o.reshape(b, t, n * d).astype(x.dtype) @ layer["wo"].astype(x.dtype)
    return out, state, conv, (s1, c1)
