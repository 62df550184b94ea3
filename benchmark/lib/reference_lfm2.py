"""The plain reference of the gated-short-convolution hybrids (``lfm2_24b``:
LFM2-24B-A2B, ``model_type`` lfm2_moe): the published block's mathematics in
straightforward ``jax.numpy``, float32 at matmul precision "highest": the
convolution as its taps' shifted products over the whole sequence, grouped-
query attention over the whole sequence, no cache, no chunks, no batching,
every expert evaluated on every token (one at a time, so that 5 120 tokens
fit beside the served weights).  It imports nothing of the program; the
sibling ``reference.py`` lends the float8 rounding, the matmul, the norm,
the half-split RoPE and the seed key.

Pre-norm residual blocks, every RMSNorm with a weight and eps ``norm_eps``,
no bias anywhere:  h = x + mixer_l(norm(x; operator_norm));  y = h +
ffn_l(norm(h; ffn_norm));  after the last layer norm(.; embedding_norm),
logits = h E^T (the head TIED to the embedding: assumed).  The
configuration file names the kind of every layer (``layer_kinds``).

Mixer ``conv``, u the normed input:
    [B | C | X] = u W_in  (H -> 3 H, in that order);  z_t = B_t * X_t;
    c_t = sum_{j < K} w[j] * z_{t-K+1+j}  (depthwise, causal, K =
    ``conv_L_cache`` taps, zeros before the sequence's start, no bias);
    out_t = (C_t * c_t) W_out.
Mixer ``full_attention``: q [N, D], k and v [N_kv, D] from u, D = H / N;
    q = RMSNorm_D(q; q_layernorm), k = RMSNorm_D(k; k_layernorm) per head,
    BEFORE RoPE; RoPE by halves over all D dimensions; causal softmax of
    q k^T / sqrt(D), N / N_kv query heads a K/V head; out_proj.
FFN of the first ``num_dense_layers`` layers: SwiGLU W_2(silu(W_1 a) * W_3 a).
FFN of the others: s = sigmoid(a W_g) in float32; the ``num_experts_per_tok``
    experts with the largest s + b (``use_expert_bias``: b for the CHOICE
    only); weights s_e / (sum of the chosen + 1e-6) (``norm_topk_prob``)
    x ``routed_scaling_factor``; experts SwiGLU; no shared expert.

``quant="fp8"`` is the CONTROL: both operands of every linear layer
rounded to float8 e4m3 (the sibling's ``_mm``).
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name):
    """A module of this directory, under the name ``run.lib`` gives it."""
    full = f"benchlib_{name}"
    if full not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            full, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[full] = mod
        spec.loader.exec_module(mod)
    return sys.modules[full]


_ref = _sibling("reference")
HIGHEST, _mm, _rms, _rope, seed_key = (_ref.HIGHEST, _ref._mm, _ref._rms,
                                       _ref._rope, _ref.seed_key)

#: the selection bias is what BALANCES the experts' load in a checkpoint
#: (its update rule: after a batch, raise the bias of an expert that got
#: fewer rows than the mean by a fixed step, lower it otherwise).  With
#: weights from a seed a hidden state has a direction all tokens share,
#: which offsets every expert's score, another way under every seed: a
#: drawn bias leaves the number of experts a decode step touches, and with
#: it the cell's speed, to the seed (``reference_ling3``'s finding, PERF.md
#: section 6, PR 31).  So the bias is fitted by that rule on PROBE_TOKENS
#: tokens drawn from the seed: BALANCE_STEPS steps of each of
#: BALANCE_RATES, the last one the usual published step size
PROBE_TOKENS, BALANCE_STEPS, BALANCE_RATES = 2048, 120, (0.01, 0.003, 0.001)


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names, at the top level of the file or under
    ``model``; the kind of every layer of the cut under ``layer_kinds``
    ("conv" / "full_attention")."""
    m = config.get("model", config)
    if m.get("conv_bias") or not m.get("use_expert_bias", True):
        raise KeyError("reference_lfm2 describes a convolution without "
                       "bias and a router with a selection bias; this "
                       "configuration states another")
    kinds = tuple(config["layer_kinds"])
    if (len(kinds) != m["num_hidden_layers"]
            or set(kinds) - {"conv", "full_attention"}):
        raise KeyError("layer_kinds does not name every layer as 'conv' "
                       "or 'full_attention'")
    heads = m["num_attention_heads"]
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "kinds": kinds,
        "heads": heads,
        "kv_heads": m["num_key_value_heads"],
        "head_dim": m.get("head_dim") or m["hidden_size"] // heads,
        "taps": m["conv_L_cache"],
        "vocab": m["vocab_size"],
        "experts": m["num_experts"],
        "top_k": m["num_experts_per_tok"],
        "inter": m["moe_intermediate_size"],
        "dense_inter": m["intermediate_size"],
        "first_dense": m["num_dense_layers"],
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "rope_theta": float(m["rope_parameters"]["rope_theta"]),
        "eps": float(m["norm_eps"]),
        "param_dtype": config.get("served", {}).get(
            "param_dtype", m.get("torch_dtype", "bfloat16")),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict):
    """The model's weights in the tree layout the program's entry points
    take (``embed``, ``final_norm``, ``lm_head``, ``layers`` of
    ``attn_norm / ffn_norm / moe`` and a mixer: a 'conv' layer's
    ``conv_win / conv_w / wo``, an attention layer's ``wq / wk / wv / wo /
    q_norm / k_norm``).  ``lm_head`` is the embedding transposed (tied: the
    program's tree keeps it as an array of its own).  A mixture layer's
    ``moe`` holds the router (``gate_w``, the selection bias ``gate_bias``
    float32, fitted so that the experts' loads balance:
    :func:`balance_biases`) and the stacked experts; a dense layer's holds
    one expert of the dense width.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, nkv, dh, v = (d["hidden"], d["heads"], d["kv_heads"],
                         d["head_dim"], d["vocab"])
    taps = d["taps"]

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    def ffn(key, n_exp, inter):
        ks = jax.random.split(key, 4)
        p = {"gate_w": nrm(ks[0], (h, n_exp), h),
             "w_up": nrm(ks[1], (n_exp, h, inter), h),
             "b_up": jnp.zeros((n_exp, inter), dt),
             "w_down": nrm(ks[2], (n_exp, inter, h), inter),
             "b_down": jnp.zeros((n_exp, h), dt),
             "w_gate": nrm(ks[3], (n_exp, h, inter), h)}
        if n_exp > 1:
            p["gate_bias"] = jnp.zeros((n_exp,), jnp.float32)  # fitted below
        return p

    def mixer(key, kind):
        ks = jax.random.split(key, 4)
        if kind == "conv":
            return {"conv_win": nrm(ks[0], (h, 3 * h), h),
                    "conv_w": nrm(ks[1], (taps, h), taps),
                    "wo": nrm(ks[2], (h, h), h)}
        return {"wq": nrm(ks[0], (h, nh * dh), h),
                "wk": nrm(ks[1], (h, nkv * dh), h),
                "wv": nrm(ks[2], (h, nkv * dh), h),
                "wo": nrm(ks[3], (nh * dh, h), nh * dh),
                "q_norm": jnp.ones((dh,), dt), "k_norm": jnp.ones((dh,), dt)}

    @functools.partial(jax.jit, static_argnames=("dense", "kind"))
    def layer(key, dense, kind):
        k0, k1 = jax.random.split(key)
        return {
            "attn_norm": jnp.ones((h,), dt), "ffn_norm": jnp.ones((h,), dt),
            **mixer(k0, kind),
            "moe": (ffn(k1, 1, d["dense_inter"]) if dense
                    else ffn(k1, d["experts"], d["inter"])),
        }

    @jax.jit
    def ends(key):
        embed = (jax.random.normal(key, (v, h), jnp.float32) * 0.02
                 ).astype(dt)
        return {"embed": embed, "final_norm": jnp.ones((h,), dt),
                "lm_head": embed.T}

    params = ends(seed_key(seed, 0))
    params["layers"] = [layer(seed_key(seed, 1 + li),
                              dense=li < d["first_dense"],
                              kind=d["kinds"][li])
                        for li in range(d["layers"])]
    return balance_biases(params, d, seed)


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def short_conv(layer, x, d, quant=None):
    """The gated short convolution over one sequence x: [T, H] float32
    (already normed): the taps as shifted products."""
    t, taps = x.shape[0], d["taps"]
    gate_b, gate_c, xs = jnp.split(_mm(x, layer["conv_win"], quant), 3,
                                   axis=-1)
    z = gate_b * xs
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    w = layer["conv_w"].astype(jnp.float32)
    c = sum(padded[j:j + t] * w[j] for j in range(taps))
    return _mm(gate_c * c, layer["wo"], quant)


def attention(layer, x, d, quant=None, q_block=512):
    """Causal grouped-query attention with a norm on every head of q and
    of k before RoPE, over one sequence x: [T, H] float32 (already
    normed), the scores in blocks of ``q_block`` rows."""
    t = x.shape[0]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(t)
    q = _mm(x, layer["wq"], quant).reshape(t, nh, dh)
    k = _mm(x, layer["wk"], quant).reshape(t, nkv, dh)
    v = _mm(x, layer["wv"], quant).reshape(t, nkv, dh)
    q = _rope(_rms(q, layer["q_norm"], d["eps"]), pos, d["rope_theta"])
    k = _rope(_rms(k, layer["k_norm"], d["eps"]), pos, d["rope_theta"])
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))

    def rows(qb, pb):
        s = jnp.einsum("tnd,snd->nts", qb, k, precision=HIGHEST) \
            / math.sqrt(dh)
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s, -1e30)
        return jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    if q_block >= t or t % q_block:
        ctx = rows(q, pos)
    else:
        nb = t // q_block
        ctx = jax.lax.map(
            lambda a: rows(*a),
            (q.reshape(nb, q_block, nh, dh),
             pos.reshape(nb, q_block))).reshape(t, nh, dh)
    return _mm(ctx.reshape(t, nh * dh), layer["wo"], quant)


def router_scores(x, gate_w):
    return jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                  gate_w.astype(jnp.float32),
                                  precision=HIGHEST))


def router_weights(x, gate_w, gate_bias, d):
    """[T, E] dense combine weights and the chosen experts [T, k]: sigmoid
    scores; the choice is the top-k of score + bias; the chosen scores
    themselves (WITHOUT the bias) over their sum + 1e-6, scaled."""
    s = router_scores(x, gate_w)
    top_i = jax.lax.top_k(s + gate_bias.astype(jnp.float32)[None, :],
                          d["top_k"])[1]
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * d["scaling"]
    cw = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, gate_w.shape[1], dtype=jnp.float32))
    return cw, top_i


def _swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def ffn(p, x, d, quant=None):
    """The feed-forward of one layer over x: [T, H] float32 (normed): one
    dense SwiGLU, or every expert on every token, one at a time, combined
    through its column of the dense weight matrix."""
    if p["gate_w"].shape[1] == 1:
        return _swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0],
                       quant)
    cw, _ = router_weights(x, p["gate_w"], p["gate_bias"], d)

    def one(acc, e):
        y = _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], quant)
        return acc + cw[:, e][:, None] * y, None

    return jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(p["w_up"].shape[0]))[0]


def _dims_key(d):
    return tuple(sorted(d.items()))


def _mixer(layer, h, d, kind, quant=None):
    return (short_conv if kind == "conv" else attention)(layer, h, d, quant)


@functools.partial(jax.jit, static_argnames=("dkey", "kind", "quant"))
def _block(layer, x, dkey, kind, quant):
    d = dict(dkey)
    x = x + _mixer(layer, _rms(x, layer["attn_norm"], d["eps"]), d, kind,
                   quant)
    return x + ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]), d,
                   quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head, x, rows, eps, quant):
    return _mm(_rms(x[rows], final_norm, eps), lm_head, quant)


@functools.partial(jax.jit, static_argnames=("dkey",))
def _fitted_bias(h, gate_w, dkey):
    """The bias that balances the experts' loads over the rows h [T, H]
    (normed): the checkpoint's update rule from zero, each step on the
    whole probe."""
    d = dict(dkey)
    s = router_scores(h, gate_w)
    n_exp = gate_w.shape[1]
    mean_load = h.shape[0] * d["top_k"] / n_exp

    def step(bias, rate):
        chosen = jax.lax.top_k(s + bias[None, :], d["top_k"])[1]
        load = jnp.zeros((n_exp,), jnp.float32).at[
            chosen.reshape(-1)].add(1.0)
        return bias + rate * jnp.sign(mean_load - load), None

    rates = jnp.repeat(jnp.asarray(BALANCE_RATES, jnp.float32),
                       BALANCE_STEPS)
    return jax.lax.scan(step, jnp.zeros((n_exp,), jnp.float32), rates)[0]


@functools.partial(jax.jit, static_argnames=("dkey", "kind"))
def _ffn_input(layer, x, dkey, kind):
    d = dict(dkey)
    x = x + _mixer(layer, _rms(x, layer["attn_norm"], d["eps"]), d, kind)
    return x, _rms(x, layer["ffn_norm"], d["eps"])


@functools.partial(jax.jit, static_argnames=("dkey",))
def _ffn_output(p, h, dkey):
    return ffn(p, h, dict(dkey))


def balance_biases(params, d, seed):
    """Fit every mixture layer's selection bias, first layer first: a
    probe sequence from the seed goes through the layers (this file's own
    forward pass), each router is balanced on the rows that reach it, and
    the probe goes on through the layer as balanced."""
    dkey = _dims_key(d)
    probe = jax.random.randint(seed_key(seed, 10_000), (PROBE_TOKENS,), 1,
                               d["vocab"])
    x = params["embed"][probe].astype(jnp.float32)
    for layer, kind in zip(params["layers"], d["kinds"]):
        x, h = _ffn_input(layer, x, dkey, kind)
        if "gate_bias" in layer["moe"]:
            layer["moe"]["gate_bias"] = _fitted_bias(
                h, layer["moe"]["gate_w"], dkey)
        x = x + _ffn_output(layer["moe"], h, dkey)
    return params


def forward_logits(params, d, tokens, rows, quant=None):
    """Reference logits of ONE sequence.  tokens: [T] int32 (padded past
    the true end: causality keeps pads out of earlier rows); rows: [R]
    int32 positions whose logits are wanted.  Layer by layer, so only one
    layer's float32 copies live at a time.  Returns [R, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    dkey = _dims_key(d)
    for layer, kind in zip(params["layers"], d["kinds"]):
        x = _block(layer, x, dkey, kind, quant)
    return _head(params["final_norm"], params["lm_head"], x, rows,
                 d["eps"], quant)


# ----------------------------------------------------------------------
# the served-model comparison (the siblings', over this forward pass)
# ----------------------------------------------------------------------

def served_token_gaps(params, d, streams, t_pad, r_pad, control=None):
    """For each served stream ``(prompt, served_tokens)``: run the
    reference once over prompt + served tokens and read, at every served
    position, how far the served token's logit lies below the reference's
    best, as a share of the largest logit magnitude among the compared
    rows.  With ``control`` (a ``quant`` name) the token read at each
    position is instead the one the lower-precision reference puts first.
    Returns ``{"widest", "mean", "tokens", "per_stream"}``."""
    widest, total, count, per = 0.0, 0.0, 0, []
    for prompt, served in streams:
        t0, n = len(prompt), len(served)
        toks = np.zeros((t_pad,), np.int32)
        toks[:t0] = prompt
        toks[t0:t0 + n] = served
        rows = np.full((r_pad,), t0 - 1, np.int32)
        rows[:n] = np.arange(t0 - 1, t0 + n - 1)
        ref = np.asarray(forward_logits(
            params, d, jnp.asarray(toks), jnp.asarray(rows)))[:n]
        if control is None:
            picked = np.asarray(served, np.int64)
        else:
            picked = np.asarray(forward_logits(
                params, d, jnp.asarray(toks), jnp.asarray(rows),
                quant=control))[:n].argmax(-1)
        scale = float(np.abs(ref).max())
        gaps = (ref.max(-1) - ref[np.arange(n), picked]) / scale
        widest = max(widest, float(gaps.max()))
        total += float(gaps.sum())
        count += n
        per.append({"prompt": t0, "served": n, "widest": float(gaps.max()),
                    "mean": float(gaps.mean()),
                    "argmax_equal": int((gaps == 0).sum())})
    return {"widest": widest, "mean": total / max(count, 1),
            "tokens": count, "per_stream": per}
