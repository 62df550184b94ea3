"""The gated short convolution: a token mixer that keeps, whatever the
context's length, the last ``conv_taps - 1`` inputs of a depthwise
convolution and nothing else; no positions, nothing cached a token.  Plain
XLA, its two forms (a span with carried inputs; one step) behind ONE
function, as :func:`~flashmoe_tpu.ops.kda.kda_attention` is.

x is the normed input of the block, ``*`` elementwise, K = ``conv_taps``:

    [B | C | X] = x W_in                  (H -> 3 H, in that order)
    z_t = B_t * X_t                       the convolution's input
    c_t = sum_{j < K} w[j] * z_{t-K+1+j}  depthwise, causal, zeros before
                                          the sequence's start, no bias
    out_t = (C_t * c_t) W_out

A slot keeps ``z_{t-1} .. z_{t-K+1}`` in the activations' dtype: z is
rounded to it BEFORE the convolution, so a span that carries its inputs in
and a span that holds them all give the same sums to the bit.
"""

from __future__ import annotations

import jax.numpy as jnp

from flashmoe_tpu.utils.telemetry import trace_span


def conv_span(z, c0, w, n_valid):
    """The convolution over a span.  z: [B, T, H]; c0: [B, (K - 1) * H] the
    carried inputs, the oldest first; w: [K, H] float32; n_valid: [B] the
    rows' valid prefixes.  Returns (c [B, T, H] float32, the inputs after
    each row's last valid position [B, (K - 1) * H])."""
    b, t, h = z.shape
    taps = w.shape[0]
    full = jnp.concatenate([c0.reshape(b, taps - 1, h), z], axis=1)
    c = sum(full[:, j:j + t].astype(jnp.float32) * w[j]
            for j in range(taps))
    c1 = jnp.take_along_axis(
        full, (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    return c, c1.reshape(b, -1)


def conv_step(z, c0, w, n_valid):
    """:func:`conv_span` for ONE token a row (decode), over the carried
    inputs as they lie (H-wide slices of a row: a [B, K - 1, H] view of
    them would be padded to whole tiles, and the chip copied the array
    into another layout and back).  z: [B, 1, H]."""
    h = z.shape[-1]
    taps = w.shape[0]
    parts = [c0[:, j * h:(j + 1) * h] for j in range(taps - 1)] + [z[:, 0]]
    c = sum(p.astype(jnp.float32) * w[j] for j, p in enumerate(parts))
    c1 = jnp.where((n_valid > 0)[:, None],
                   jnp.concatenate([c0[:, h:], z[:, 0]], axis=-1), c0)
    return c[:, None], c1


def conv_attention(layer, x, cfg, conv, si, valid=None, slots=None,
                   fresh=None):
    """THE 'conv' mixer of every cached path, with ``kda_attention``'s
    contract: project a span of T tokens a row, run the convolution from
    the rows' carried inputs (:func:`conv_step` for T = 1,
    :func:`conv_span` for a longer span), write the span's last inputs
    back.

    x: [B, T, H] normed; conv: [L_s, S, (K - 1) * H] the per-SLOT inputs of
    every 'conv' layer (a slot's K - 1 inputs side by side, the oldest
    first), or None for a whole prompt at once (it starts from nothing);
    si: this layer's index among them; valid: [B, T] bool, a PREFIX of
    each row (None: all); slots: [B] the slot each row owns (None: row b
    owns slot b); fresh: scalar bool, the rows start from nothing whatever
    the slots hold (a prompt's first chunk).  Returns (the block's output
    [B, T, H], conv, the rows' inputs after their last valid position
    ``(c1 [B, (K - 1) * H],)``: a row with nothing valid keeps what it
    had, to the bit)."""
    b, t, h = x.shape
    taps = cfg.conv_taps
    gate_b, gate_c, xs = jnp.split(x @ layer["conv_win"].astype(x.dtype), 3,
                                   axis=-1)
    z = gate_b * xs                                          # [B, T, H]
    if conv is None:
        c0 = jnp.zeros((b, (taps - 1) * h), z.dtype)
    else:
        c0 = conv[si] if slots is None else conv[si, slots]
        if fresh is not None:
            c0 = jnp.where(fresh, jnp.zeros((), c0.dtype), c0)
        c0 = c0.astype(z.dtype)
    n_valid = (jnp.full((b,), t, jnp.int32) if valid is None
               else jnp.sum(valid, axis=1, dtype=jnp.int32))
    w = layer["conv_w"].astype(jnp.float32)                  # [K, H]
    if t == 1:
        with trace_span("attn.conv_decode"):
            c, c1 = conv_step(z, c0, w, n_valid)
    else:
        with trace_span("attn.conv_prefill"):
            c, c1 = conv_span(z, c0, w, n_valid)
    y = (gate_c.astype(jnp.float32) * c).astype(x.dtype)
    if conv is not None:
        c1 = c1.astype(conv.dtype)
        conv = (conv.at[si].set(c1) if slots is None
                else conv.at[si, slots].set(c1))
    return y @ layer["wo"].astype(x.dtype), conv, (c1,)
