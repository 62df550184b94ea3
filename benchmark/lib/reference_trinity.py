"""The plain reference of the window / full attention mixtures
(``trinity_large``: Trinity-Large-Preview, ``model_type`` afmoe): the
published block's mathematics in straightforward ``jax.numpy``, float32 at
matmul precision "highest": attention over the whole sequence in blocks of
query rows (so that a 30 000-token stream fits beside the served weights),
no cache, no pages, no chunks, no batching, every expert HELD here evaluated
on every token (one at a time).  It imports nothing of the program; the
sibling ``reference.py`` lends the float8 rounding, the matmul, the norm,
the half-split RoPE and the seed key.

    x = embed[ids] * sqrt(H)                               (``mup_enabled``)
    layer i, FOUR RMSNorms with weights of their own (eps ``rms_norm_eps``):
        x = x + post_attention_norm(Attn_i(input_norm(x)))
        x = x + post_mlp_norm(FFN_i(pre_mlp_norm(x)))
    logits = norm(x) lm_head                               (untied)

Attn_i(u): q = u Wq [N, D], k = u Wk, v = u Wv [N_kv, D], g = u Wg [N, D];
    every head of q and of k through RMSNorm over its D (``q_norm`` /
    ``k_norm``).  A ``sliding_attention`` layer rotates q and k (RoPE by
    halves over all D, theta ``rope_theta``) and a query at position p sees
    the keys ``p - sliding_window < j <= p`` (``sliding_window`` keys, itself
    among them); a ``full_attention`` layer applies NO rotation and sees
    every ``j <= p``.  softmax(q k^T / sqrt(D)) v, N / N_kv query heads a
    K/V head; the heads' outputs times sigmoid(g), elementwise; then Wo.
FFN_i, i < ``num_dense_layers``: SwiGLU of width ``intermediate_size``.
FFN_i, others: s = sigmoid(u Wr) in float32 over ALL published experts;
    the ``num_experts_per_tok`` with the largest s + b chosen (b for the
    CHOICE only); weights s_e / (sum of the chosen + 1e-20) (``route_norm``)
    x ``route_scale``; SwiGLU experts of width ``moe_intermediate_size``, of
    which THIS chip holds experts ``expert_first`` .. + ``num_experts`` - 1
    (the others' part is left out, as the program leaves it out); plus ONE
    shared SwiGLU expert of that width on every token.

``quant`` names a CONTROL, something else put in this reference's place:
"fp8" (both operands of every linear layer rounded to float8 e4m3, the
sibling's ``_mm``) or one mechanism wrong (``CONTROLS``): the window one key
short, the full layers rotated, the output gate left out, the parts' output
norms left out, the selection bias added to the weights.
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

#: the mistakes a ``quant`` may name beside "fp8"
CONTROLS = ("fp8", "window_short", "rope_on_full", "no_gate",
            "no_out_norm", "bias_in_weights")

#: the selection bias is FITTED, as the siblings' (``reference_lfm2``): a
#: checkpoint's bias balances the experts' load, and a drawn one leaves the
#: experts a decode step touches, and the cell's speed, to the seed
PROBE_TOKENS, BALANCE_STEPS, BALANCE_RATES = 2048, 120, (0.01, 0.003, 0.001)


def _fp8(quant):
    """What the linear layers are told of a control: float8 or nothing."""
    return quant if quant == "fp8" else None


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names at the top level of the file; the published
    expert count under ``published``, the share held under ``held``, the
    kind of every layer of the cut under ``layer_kinds``."""
    m = config
    if (m["score_func"] != "sigmoid" or m["n_group"] != 1
            or not m["route_norm"] or not m["mup_enabled"]
            or m["tie_word_embeddings"] or m["num_shared_experts"] != 1
            or m.get("rope_scaling") is not None):
        raise KeyError("reference_trinity describes a sigmoid router of one "
                       "group with normalised weights, one shared expert, a "
                       "scaled embedding, an untied head and plain RoPE; "
                       "this configuration states another")
    kinds = tuple(config["layer_kinds"])
    if (len(kinds) != m["num_hidden_layers"]
            or set(kinds) - {"sliding_attention", "full_attention"}):
        raise KeyError("layer_kinds does not name every layer as "
                       "'sliding_attention' or 'full_attention'")
    held = config.get("held", {})
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "kinds": kinds,
        "heads": m["num_attention_heads"],
        "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"],
        "window": m["sliding_window"],
        "vocab": m["vocab_size"],
        "router_experts": config.get("published", m)["num_experts"],
        "experts": m["num_experts"],
        "expert_first": held.get("expert_first", 0),
        "top_k": m["num_experts_per_tok"],
        "inter": m["moe_intermediate_size"],
        "dense_inter": m["intermediate_size"],
        "first_dense": m["num_dense_layers"],
        "scaling": float(m["route_scale"]),
        "embed_mult": math.sqrt(m["hidden_size"]),
        "rope_theta": float(m["rope_theta"]),
        "eps": float(m["rms_norm_eps"]),
        "param_dtype": config.get("served", {}).get("param_dtype",
                                                    "bfloat16"),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict):
    """The model's weights in the tree layout the program's entry points
    take (``embed``, ``final_norm``, ``lm_head``, ``layers`` of the four
    norms ``attn_norm / attn_out_norm / ffn_norm / ffn_out_norm``, the
    attention's ``wq / wk / wv / wg / wo / q_norm / k_norm`` and ``moe``).
    A mixture layer's ``moe`` holds the router over ALL published experts
    (``gate_w``, the selection bias ``gate_bias`` float32, fitted so that
    the experts' loads balance: :func:`balance_biases`), the stacked
    weights of the experts HELD and the shared expert; a dense layer's
    holds one expert of the dense width.  The norms' weights are drawn near
    one (0.5 .. 1.5), so that a norm left out or applied with another's
    weights shows.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, nkv, dh, v = (d["hidden"], d["heads"], d["kv_heads"],
                         d["head_dim"], d["vocab"])

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    def near_one(k, n):
        return jax.random.uniform(k, (n,), jnp.float32, 0.5, 1.5).astype(dt)

    def ffn(key, n_route, n_exp, inter, shared):
        ks = jax.random.split(key, 7)
        p = {"gate_w": nrm(ks[0], (h, n_route), h),
             "w_up": nrm(ks[1], (n_exp, h, inter), h),
             "b_up": jnp.zeros((n_exp, inter), dt),
             "w_down": nrm(ks[2], (n_exp, inter, h), inter),
             "b_down": jnp.zeros((n_exp, h), dt),
             "w_gate": nrm(ks[3], (n_exp, h, inter), h)}
        if n_route > 1:
            p["gate_bias"] = jnp.zeros((n_route,), jnp.float32)  # fitted below
        if shared:
            p["shared_w_up"] = nrm(ks[4], (h, inter), h)
            p["shared_w_down"] = nrm(ks[5], (inter, h), inter)
            p["shared_w_gate"] = nrm(ks[6], (h, inter), h)
        return p

    @functools.partial(jax.jit, static_argnames=("dense",))
    def layer(key, dense):
        ks = jax.random.split(key, 12)
        return {
            "attn_norm": near_one(ks[0], h),
            "attn_out_norm": near_one(ks[1], h),
            "ffn_norm": near_one(ks[2], h),
            "ffn_out_norm": near_one(ks[3], h),
            "wq": nrm(ks[4], (h, nh * dh), h),
            "wk": nrm(ks[5], (h, nkv * dh), h),
            "wv": nrm(ks[6], (h, nkv * dh), h),
            "wg": nrm(ks[7], (h, nh * dh), h),
            "wo": nrm(ks[8], (nh * dh, h), nh * dh),
            "q_norm": near_one(ks[9], dh), "k_norm": near_one(ks[10], dh),
            "moe": (ffn(ks[11], 1, 1, d["dense_inter"], False) if dense
                    else ffn(ks[11], d["router_experts"], d["experts"],
                             d["inter"], True)),
        }

    @jax.jit
    def ends(key):
        k0, k1, k2 = jax.random.split(key, 3)
        return {"embed": (jax.random.normal(k0, (v, h), jnp.float32)
                          * 0.02).astype(dt),
                "final_norm": near_one(k2, h),
                "lm_head": nrm(k1, (h, v), h)}

    params = ends(seed_key(seed, 0))
    params["layers"] = [layer(seed_key(seed, 1 + li),
                              dense=li < d["first_dense"])
                        for li in range(d["layers"])]
    return balance_biases(params, d, seed)


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def attention(layer, x, d, kind, quant=None, q_block=256):
    """One attention layer over one sequence x: [T, H] float32 (already
    normed), the scores in blocks of ``q_block`` query rows and one K/V
    head's query heads at a time.  A window layer's block reads the
    ``window + q_block`` keys that end at its last row (the sequence padded
    in front by ``window`` rows that no query sees)."""
    t = x.shape[0]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    mm = _fp8(quant)
    sliding = kind == "sliding_attention"
    window = d["window"] - (quant == "window_short")
    pos = jnp.arange(t)
    q = _rms(_mm(x, layer["wq"], mm).reshape(t, nh, dh), layer["q_norm"],
             d["eps"])
    k = _rms(_mm(x, layer["wk"], mm).reshape(t, nkv, dh), layer["k_norm"],
             d["eps"])
    v = _mm(x, layer["wv"], mm).reshape(t, nkv, dh)
    gate = _mm(x, layer["wg"], mm)
    if sliding or quant == "rope_on_full":
        q = _rope(q, pos, d["rope_theta"])
        k = _rope(k, pos, d["rope_theta"])
    if q_block >= t or t % q_block:
        q_block = t
    nb = t // q_block
    # [N_kv, nb, q_block, rep, D]: one K/V head's queries, block by block
    qg = q.reshape(nb, q_block, nkv, nh // nkv, dh).transpose(2, 0, 1, 3, 4)
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # [N_kv, T, D]
    span = min(window, t) + q_block if sliding else t
    front = span - q_block

    def rows(args):
        qb, b, kk, vv = args            # [q_block, rep, D], the block's index
        pq = b * q_block + jnp.arange(q_block)
        if sliding:                     # keys [b q_block - front, ... + span)
            lo = b * q_block
            kb = jax.lax.dynamic_slice(kk, (lo, 0), (span, dh))
            vb = jax.lax.dynamic_slice(vv, (lo, 0), (span, dh))
            pk = lo - front + jnp.arange(span)
        else:
            kb, vb, pk = kk, vv, pos
        s = jnp.einsum("trd,sd->rts", qb, kb, precision=HIGHEST) \
            / math.sqrt(dh)
        seen = (pk[None, :] <= pq[:, None]) & (pk[None, :] >= 0)
        if sliding:
            seen &= pk[None, :] > pq[:, None] - window
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("rts,sd->trd", p, vb, precision=HIGHEST)

    def head(args):
        qh, kk, vv = args               # [nb, q_block, rep, D], [T, D] x 2
        if sliding:                     # rows no query sees, in front
            kk = jnp.concatenate([jnp.zeros((front, dh), kk.dtype), kk])
            vv = jnp.concatenate([jnp.zeros((front, dh), vv.dtype), vv])
        return jax.lax.map(
            lambda a: rows((a[0], a[1], kk, vv)), (qh, jnp.arange(nb)))

    ctx = jax.lax.map(head, (qg, kh, vh))      # [N_kv, nb, q_block, rep, D]
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(t, nh * dh)
    if quant != "no_gate":
        ctx = ctx * jax.nn.sigmoid(gate)
    return _mm(ctx, layer["wo"], mm)


def router_scores(x, gate_w):
    return jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                  gate_w.astype(jnp.float32),
                                  precision=HIGHEST))


def router_weights(x, gate_w, gate_bias, d, quant=None):
    """[T, E] dense combine weights over ALL published experts and the
    chosen experts [T, k]: sigmoid scores; the choice is the top-k of score
    + bias; the chosen scores themselves (WITHOUT the bias) over their sum
    + 1e-20, times ``route_scale``."""
    s = router_scores(x, gate_w)
    biased = s + gate_bias.astype(jnp.float32)[None, :]
    top_i = jax.lax.top_k(biased, d["top_k"])[1]
    w = jnp.take_along_axis(biased if quant == "bias_in_weights" else s,
                            top_i, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * d["scaling"]
    cw = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, gate_w.shape[1], dtype=jnp.float32))
    return cw, top_i


def _swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def ffn(p, x, d, quant=None, shared=True):
    """The feed-forward of one layer over x: [T, H] float32 (normed): one
    dense SwiGLU, or every expert HELD here on every token, one at a time,
    combined through its column of the dense weight matrix, plus
    (``shared``) the shared expert."""
    mm = _fp8(quant)
    if p["gate_w"].shape[1] == 1:
        return _swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0], mm)
    cw, _ = router_weights(x, p["gate_w"], p["gate_bias"], d, quant)

    def one(acc, e):
        y = _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], mm)
        return acc + cw[:, d["expert_first"] + e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(p["w_up"].shape[0]))
    if shared:
        out = out + _swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                            p["shared_w_down"], mm)
    return out


def _dims_key(d):
    return tuple(sorted(d.items()))


def _out_norm(layer, name, y, d, quant):
    return y if quant == "no_out_norm" else _rms(y, layer[name], d["eps"])


@functools.partial(jax.jit, static_argnames=("dkey", "kind", "quant"))
def _attn_part(layer, x, dkey, kind, quant=None):
    """x after the layer's attention part, and its feed-forward part's
    normed input."""
    d = dict(dkey)
    a = attention(layer, _rms(x, layer["attn_norm"], d["eps"]), d, kind,
                  quant)
    x = x + _out_norm(layer, "attn_out_norm", a, d, quant)
    return x, _rms(x, layer["ffn_norm"], d["eps"])


@functools.partial(jax.jit, static_argnames=("dkey", "quant"))
def _ffn_part(layer, x, u, dkey, quant=None):
    d = dict(dkey)
    return x + _out_norm(layer, "ffn_out_norm",
                         ffn(layer["moe"], u, d, quant), d, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head, x, rows, eps, quant):
    return _mm(_rms(x[rows], final_norm, eps), lm_head, _fp8(quant))


@functools.partial(jax.jit, static_argnames=("dkey",))
def _fitted_bias(u, gate_w, dkey):
    """The bias that balances the experts' loads over the rows u [T, H]
    (normed): the checkpoint's update rule from zero, each step on the
    whole probe."""
    d = dict(dkey)
    s = router_scores(u, gate_w)
    n_exp = gate_w.shape[1]
    mean_load = u.shape[0] * d["top_k"] / n_exp

    def step(bias, rate):
        chosen = jax.lax.top_k(s + bias[None, :], d["top_k"])[1]
        load = jnp.zeros((n_exp,), jnp.float32).at[
            chosen.reshape(-1)].add(1.0)
        return bias + rate * jnp.sign(mean_load - load), None

    rates = jnp.repeat(jnp.asarray(BALANCE_RATES, jnp.float32),
                       BALANCE_STEPS)
    return jax.lax.scan(step, jnp.zeros((n_exp,), jnp.float32), rates)[0]


def balance_biases(params, d, seed):
    """Fit every mixture layer's selection bias, first layer first: a
    probe sequence from the seed goes through the layers (this file's own
    forward pass), each router is balanced on the rows that reach it, and
    the probe goes on through the layer as balanced."""
    dkey = _dims_key(d)
    probe = jax.random.randint(seed_key(seed, 10_000), (PROBE_TOKENS,), 1,
                               d["vocab"])
    x = params["embed"][probe].astype(jnp.float32) * d["embed_mult"]
    for layer, kind in zip(params["layers"], d["kinds"]):
        x, u = _attn_part(layer, x, dkey, kind)
        if "gate_bias" in layer["moe"]:
            layer["moe"]["gate_bias"] = _fitted_bias(
                u, layer["moe"]["gate_w"], dkey)
        x = _ffn_part(layer, x, u, dkey)
    return params


def forward_logits(params, d, tokens, rows, quant=None):
    """Reference logits of ONE sequence.  tokens: [T] int32 (padded past
    the true end: causality keeps pads out of earlier rows); rows: [R]
    int32 positions whose logits are wanted; ``quant``: None or one of
    ``CONTROLS``.  Layer by layer, so only one layer's float32 copies live
    at a time.  Returns [R, V] float32."""
    if quant not in (None, *CONTROLS):
        raise ValueError(f"control {quant!r} not of {CONTROLS}")
    x = params["embed"][tokens].astype(jnp.float32) * d["embed_mult"]
    dkey = _dims_key(d)
    for layer, kind in zip(params["layers"], d["kinds"]):
        x, u = _attn_part(layer, x, dkey, kind, quant)
        x = _ffn_part(layer, x, u, dkey, quant)
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
    rows.  With ``control`` (a name of ``CONTROLS``) the token read at each
    position is instead the one the control puts first.  The sequence is
    padded to the next multiple of 2048 rows past its end, not to ``t_pad``
    (which bounds it): a 5 000-token stream then costs a fifth of a
    30 000-token one, and the streams' lengths fall into few shapes.
    Returns
    ``{"widest", "mean", "tokens", "per_stream"}``."""
    widest, total, count, per = 0.0, 0.0, 0, []
    for prompt, served in streams:
        t0, n = len(prompt), len(served)
        t_run = min(t_pad, -(-(t0 + n) // 2048) * 2048)
        toks = np.zeros((t_run,), np.int32)
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
