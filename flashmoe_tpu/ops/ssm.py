"""A selective state-space mixer in its scalar-decay form (Mamba-2's SSD),
in plain XLA, its two forms behind ONE function, as
:func:`~flashmoe_tpu.ops.kda.kda_attention` is.

An 'ssm' layer keeps, whatever the context's length, a float32 state
``S [P, N]`` a head and the last ``ssm_conv - 1`` inputs of its
convolution; nothing a token, no positions.  u is the normed input of the
block, h indexes the n heads of width P, g(h) = h // (n / G) the group
whose input and output maps head h reads, K = ``ssm_conv``:

    [z | xBC | dt~] = u W_in          (H -> n P | n P + 2 G N | n, no bias)
    xBC_t <- silu(sum_{j < K} w[j] * xBC_{t-K+1+j} + b)   depthwise, causal
    [x_t (n, P) | B_t (G, N) | C_t (G, N)] = xBC_t
    dt_t,h = softplus(dt~_t,h + dt_bias_h);   A_h = -exp(A_log_h)
    S_t,h = exp(dt_t,h A_h) S_{t-1},h + dt_t,h x_t,h (x) B_t,g(h)
    y_t,h = S_t,h C_t,g(h) + D_h x_t,h
    out = RMSNorm_groups(y * silu(z); w) W_out   (the norm over each of
                                      the G groups of n P / G channels)

:func:`ssm_step` is the recurrence, one token (decode): every row's state
read once and written once.  :func:`ssm_chunked` is the same numbers a
chunk of ``ssm_chunk`` tokens at a time (prefill, chunked prefill): with
a_t = dt_t A_h <= 0 and G_t its running sum inside the chunk,

    y_t = sum_{i <= t} exp(G_t - G_i) (C_t . B_i) dt_i x_i
          + exp(G_t) S_in C_t + D x_t
    S_out = exp(G_end) S_in + sum_i exp(G_end - G_i) dt_i x_i (x) B_i

all chunks at once but for the state, which a scan carries from chunk to
chunk.  Every exponent is a sum of a_t over a span that runs FORWARD in
time, so none is positive.  A position that is not ``valid`` has dt = 0:
decay 1, input 0, the state as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashmoe_tpu.config import LANE
from flashmoe_tpu.utils.telemetry import trace_span

_HI = dict(precision=jax.lax.Precision.HIGHEST,
           preferred_element_type=jnp.float32)


def _conv_silu(parts, w, b):
    """silu of the taps' sum: ``parts[j]`` the input ``K - 1 - j`` tokens
    back, float32 sums."""
    y = sum(p.astype(jnp.float32) * w[j] for j, p in enumerate(parts))
    return jax.nn.silu(y + b)


def ssm_project(layer, x, cfg, conv, valid):
    """x: [B, T, H] normed; conv: [B, (K - 1) * W] the convolution's last
    inputs, the oldest first, W = ``cfg.ssm_conv_width`` (None: zeros);
    valid: [B, T] bool, a PREFIX of each row (None: all).  Returns the
    gate z [B, T, n P], x [B, T, n, P], B and C [B, T, G, N] and dt
    [B, T, n] float32 (dt 0 where not valid), and the convolution's inputs
    after the row's last valid position [B, (K - 1) * W]."""
    b, t, _ = x.shape
    n, p, g, ns = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                   cfg.ssm_state)
    di, width, taps = cfg.ssm_inner, cfg.ssm_conv_width, cfg.ssm_conv
    u = x @ layer["ssm_win"].astype(x.dtype)       # [B, T, 2 n P + 2 G N + n]
    z, xbc, dt = u[..., :di], u[..., di:di + width], u[..., di + width:]
    if conv is None:
        conv = jnp.zeros((b, (taps - 1) * width), xbc.dtype)
    conv = conv.astype(xbc.dtype)
    w = layer["ssm_conv_w"].astype(jnp.float32)              # [K, W]
    bias = layer["ssm_conv_b"].astype(jnp.float32)
    n_valid = (jnp.full((b,), t, jnp.int32) if valid is None
               else jnp.sum(valid, axis=1, dtype=jnp.int32))
    if t == 1:
        # the carried inputs as they lie (W-wide slices of a row: a
        # [B, K - 1, W] view of them would be padded to whole tiles)
        old = [conv[:, j * width:(j + 1) * width] for j in range(taps - 1)]
        y = _conv_silu(old + [xbc[:, 0]], w, bias)[:, None]
        conv = jnp.where((n_valid > 0)[:, None],
                         jnp.concatenate(old[1:] + [xbc[:, 0]], axis=-1),
                         conv)
    else:
        full = jnp.concatenate([conv.reshape(b, taps - 1, width), xbc],
                               axis=1)
        y = _conv_silu([full[:, j:j + t] for j in range(taps)], w, bias)
        conv = jnp.take_along_axis(
            full, (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None],
            axis=1).reshape(b, -1)
    xs = y[..., :di].reshape(b, t, n, p)
    bm = y[..., di:di + g * ns].reshape(b, t, g, ns)
    cm = y[..., di + g * ns:].reshape(b, t, g, ns)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + layer["ssm_dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[:, :, None], dt, 0.0)
    return z, xs, bm, cm, dt, conv


def ssm_step(xs, bm, cm, dt, a, d, state):
    """The recurrence, one token.  xs: [B, n, P]; bm, cm: [B, G, N]; dt:
    [B, n]; a, d: [n] (a < 0); state: [B, n, P, N] float32.  Returns
    (y [B, n, P], the new state).  Multiplies and sums over the state as
    it lies, the heads of a group side by side: no matrix unit, float32
    throughout, and a row whose dt is 0 keeps its state.  Both results
    are taken from the state as it CAME (``S_t C_t = decay S_{t-1} C_t +
    dt x (B_t . C_t)``): the new state then has one reader, the array it
    is written back into."""
    b, n, p = xs.shape
    g = bm.shape[1]
    by_group = lambda v: v.reshape(b, g, n // g, *v.shape[2:])
    decay = by_group(jnp.exp(dt * a))[..., None]             # [B, G, r, 1]
    dtx = by_group(dt[..., None] * xs)                       # [B, G, r, P]
    s0 = by_group(state)
    s0_c = jnp.sum(s0 * cm[:, :, None, None, :], axis=-1)
    b_c = jnp.sum(bm * cm, axis=-1)[:, :, None, None]
    s = decay[..., None] * s0 + dtx[..., None] * bm[:, :, None, None, :]
    y = (decay * s0_c + dtx * b_c).reshape(b, n, p)
    return y + d[:, None] * xs, s.reshape(state.shape)


# ----------------------------------------------------------------------
# The decode step as a kernel: every slot's state read ONCE, written once
# ----------------------------------------------------------------------
#
# In plain XLA the step is two fusions a layer, the output's sums (a
# reduction over the state) and the update (an in-place
# dynamic-update-slice): the layer's [S, n, P, N] slice is read twice and
# written once, and at 256 slots of [64, 64, 128] the chip's compiler,
# short of memory, rematerialised the first layer's update (PERF.md
# section 6, PR 39).  This kernel walks the slots: slot b's state of layer
# ``li`` (a scalar, in SMEM before the body runs) comes into VMEM as one
# [n, P, N] block, double-buffered, each head's [P, N] tile is decayed and
# given its rank-one input, multiplied by C and summed over its lanes, and
# goes back to where it lay: the state array is aliased to the output and
# no XLA instruction touches it, so it is never copied.  What varies by
# ROW of a tile (dt x) comes as a column of a [P, n] array (head h in lane
# h: a lane slice, broadcast along the lanes); what varies by LANE (B, C
# of the head's group) or not at all (the head's decay) as rows.  The
# output's sums over the lanes are a product on the matrix unit.

def ssm_step_arm(b: int, slots, state) -> str:
    """The arm a decode step of ``b`` rows takes over ``state``
    ``[L_s, S, n, P, N]``: ``"step_kernel"`` (:func:`ssm_step_pallas`) on
    a TPU when row b IS slot b for every slot (the decode program), the
    state's last dimension is whole lanes, a head's rows whole sublane
    tiles and the heads fit the lanes of one output tile; ``"xla"``
    (:func:`ssm_step`) for everything else."""
    if state is None or slots is not None:
        return "xla"
    _, s, n, p, ns = state.shape
    fits = (b == s and ns % LANE == 0 and p % 8 == 0 and n <= LANE
            and state.dtype == jnp.float32)
    return ("step_kernel" if fits and jax.default_backend() == "tpu"
            else "xla")


def _ssm_step_kernel(li_ref, s_ref, dec_ref, dtx_ref, b_ref, c_ref, o_ref,
                     y_ref, *, heads, groups, y_dtype):
    """Grid: (S,), one slot a step.  li_ref: [1] the layer.  s_ref / o_ref:
    [1, 1, n, P, N] the slot's state in and out; dec_ref: [1, n, N] head
    h's decay along row h; dtx_ref: [1, P, n] dt x, head h down column h;
    b_ref, c_ref: [1, G, N]; y_ref: [1, P, LANE], the output of head h in
    lane h.  The output's sums go through the matrix unit, the new state
    against C laid along every row of a [LANE, N] operand (so every lane
    of the product holds the sum), operands in ``y_dtype``, float32
    accumulation: what every other product of the model is given."""
    del li_ref
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape[1:], 1)
    y = jnp.zeros(y_ref.shape[1:], jnp.float32)
    for g in range(groups):
        b_row = b_ref[0, g:g + 1, :]
        c_rows = jnp.broadcast_to(c_ref[0, g:g + 1, :],
                                  (y_ref.shape[2], c_ref.shape[2]))
        for h in range(g * (heads // groups), (g + 1) * (heads // groups)):
            s = (dec_ref[0, h:h + 1, :] * s_ref[0, 0, h]
                 + dtx_ref[0, :, h:h + 1] * b_row)
            o_ref[0, 0, h] = s
            sums = jax.lax.dot_general(
                s.astype(y_dtype), c_rows.astype(y_dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [P, LANE]
            y = jnp.where(lane == h, sums, y)
    y_ref[0] = y


@functools.partial(jax.jit, static_argnames=("y_dtype", "interpret"))
def ssm_step_pallas(state, li, xs, bm, cm, dt, a, *, y_dtype=jnp.float32,
                    interpret=False):
    """:func:`ssm_step` (without the skip ``D x``) over layer ``li`` of
    the WHOLE state array ``[L_s, S, n, P, N]``, in place.  xs: [S, n, P];
    bm, cm: [S, G, N]; dt: [S, n]; a: [n]; ``y_dtype``: the operands'
    dtype of the output's sums (the activations').  Returns (y [S, n, P],
    the state array).  Jitted with the layer's arrays as operands: the
    state layers of a program share one traced and lowered function."""
    _, s, n, p, ns = state.shape
    g = bm.shape[1]
    decay = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], (s, n, ns))
    dtx = jnp.swapaxes(dt[..., None] * xs, 1, 2)             # [S, P, n]
    slot = lambda *block: pl.BlockSpec(
        block, lambda b, li: (b,) + (0,) * (len(block) - 1))
    layer = pl.BlockSpec((1, 1, n, p, ns),
                         lambda b, li: (li[0], b, 0, 0, 0))
    state, y = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=n, groups=g,
                          y_dtype=y_dtype),
        name="fm_ssm_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s,),
            in_specs=[layer, slot(1, n, ns), slot(1, p, n),
                      slot(1, g, ns), slot(1, g, ns)],
            out_specs=[layer, slot(1, p, LANE)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, p, LANE), jnp.float32)],
        input_output_aliases={1: 0},
        cost_estimate=pl.CostEstimate(
            flops=5 * s * n * p * ns, transcendentals=0,
            bytes_accessed=2 * s * n * p * ns * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(jnp.asarray(li, jnp.int32).reshape(1), state, decay, dtx,
      bm.astype(jnp.float32), cm.astype(jnp.float32))
    return jnp.swapaxes(y[:, :, :n], 1, 2), state


def ssm_chunked(xs, bm, cm, dt, a, d, state, chunk):
    """The same numbers as :func:`ssm_step` token by token, ``chunk``
    tokens at a time.  xs: [B, T, n, P]; bm, cm: [B, T, G, N]; dt:
    [B, T, n]; state: [B, n, P, N] float32.  Returns (y [B, T, n, P], the
    state after the span).  T is padded to whole chunks with positions of
    dt 0, which leave the state alone."""
    b, t, n, p = xs.shape
    g, ns = bm.shape[2:]
    r = n // g
    c = min(chunk, t)
    pad = -t % c
    if pad:
        xs, bm, cm, dt = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (xs, bm, cm, dt))
    nc = (t + pad) // c
    xs_c = xs.reshape(b, nc, c, g, r, p)
    bm, cm = (v.reshape(b, nc, c, g, ns) for v in (bm, cm))
    dt = dt.reshape(b, nc, c, g, r)
    run = jnp.cumsum(dt * a.reshape(g, r), axis=2)           # G_t <= 0
    dtx = dt[..., None] * xs_c                         # [B, k, i, G, r, P]
    # inside a chunk: the masked products with the decay between i and t
    cb = jnp.einsum("bktgn,bkign->bkgti", cm, bm, **_HI)
    run_t = run.transpose(0, 1, 3, 4, 2)                     # [B, k, G, r, c]
    seen = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    between = jnp.exp(jnp.where(
        seen, run_t[..., :, None] - run_t[..., None, :], -jnp.inf))
    y = jnp.einsum("bkgrti,bkigrp->bktgrp", cb[:, :, :, None] * between,
                   dtx, **_HI)
    # between chunks: what each adds to the state, and the state it meets
    end = run[:, :, -1]                                      # [B, k, G, r]
    adds = jnp.einsum("bkigrp,bkign->bkgrpn",
                      dtx * jnp.exp(end[:, :, None] - run)[..., None], bm,
                      **_HI)

    def carry(s, x):
        keep, add = x
        return keep[..., None, None] * s + add, s

    chunks_first = lambda v: jnp.moveaxis(v, 1, 0)
    state, met = jax.lax.scan(
        carry, state.reshape(b, g, r, p, ns),
        (chunks_first(jnp.exp(end)), chunks_first(adds)))
    y = y + jnp.exp(run)[..., None] * jnp.einsum(
        "bktgn,kbgrpn->bktgrp", cm, met, **_HI)
    y = y.reshape(b, nc * c, n, p)[:, :t]
    return (y + d[:, None] * xs[:, :t],
            state.reshape(b, n, p, ns))


def ssm_attention(layer, x, cfg, state, conv, si, valid=None, slots=None,
                  fresh=None):
    """THE 'ssm' mixer of every cached path, with ``kda_attention``'s
    contract: project a span of T tokens a row, run the recurrence from
    the rows' state (:func:`ssm_step` for T = 1, :func:`ssm_chunked` for
    a longer span), write the state back.

    x: [B, T, H] normed; state: [L_s, S, n, P, N] float32 and conv:
    [L_s, S, (K - 1) * W] (a slot's K - 1 inputs side by side), the
    per-SLOT state of every 'ssm' layer, or None for a whole prompt at
    once (it starts from nothing); si: this layer's index among them;
    valid: [B, T] bool, a prefix of each row (None: all); slots: [B] the
    slot each row owns (None: row b owns slot b); fresh: scalar bool, the
    rows start from nothing whatever the slots hold (a prompt's first
    chunk).  Returns (the block's output [B, T, H], state, conv, the rows'
    state after the span ``(s [B, n, P, N], conv [B, (K - 1) * W])``: a
    row with nothing valid keeps both as they were)."""
    b, t, _ = x.shape
    n, p = cfg.ssm_heads, cfg.ssm_head_dim
    if state is None:
        s0 = c0 = None
    else:
        s0, c0 = ((state[si], conv[si]) if slots is None
                  else (state[si, slots], conv[si, slots]))
        if fresh is not None:
            s0 = jnp.where(fresh, 0.0, s0)
            c0 = jnp.where(fresh, jnp.zeros((), c0.dtype), c0)
    z, xs, bm, cm, dt, c1 = ssm_project(layer, x, cfg, c0, valid)
    if s0 is None:
        s0 = jnp.zeros((b, n, p, cfg.ssm_state), jnp.float32)
    a = -jnp.exp(layer["ssm_A_log"].astype(jnp.float32))
    d = layer["ssm_D"].astype(jnp.float32)
    in_place = (t == 1 and fresh is None
                and ssm_step_arm(b, slots, state) == "step_kernel")
    if in_place:
        with trace_span("attn.ssm_decode"):
            y, state = ssm_step_pallas(
                state, si, xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], a,
                y_dtype=x.dtype, interpret=jax.default_backend() != "tpu")
            y = (y + d[:, None] * xs[:, 0])[:, None]
            s1 = state[si]
    elif t == 1:
        with trace_span("attn.ssm_decode"):
            y, s1 = ssm_step(xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], a, d,
                             s0)
            y = y[:, None]
    else:
        with trace_span("attn.ssm_prefill"):
            y, s1 = ssm_chunked(xs, bm, cm, dt, a, d, s0, cfg.ssm_chunk)
    if state is not None:
        at = (lambda arr: arr.at[si]) if slots is None else (
            lambda arr: arr.at[si, slots])
        c1 = c1.astype(conv.dtype)
        conv = at(conv).set(c1)
        if not in_place:                     # else written where it lay
            state = at(state).set(s1)
    # the gated norm: the gate first, then an RMSNorm over each group
    y = y.reshape(b, t, cfg.ssm_groups, -1) * jax.nn.silu(
        z.astype(jnp.float32)).reshape(b, t, cfg.ssm_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = y.reshape(b, t, n * p) * layer["ssm_norm"].astype(jnp.float32)
    return y.astype(x.dtype) @ layer["wo"].astype(x.dtype), state, conv, \
        (s1, c1)
