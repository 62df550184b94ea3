"""The plain reference of ``granite4_h_micro`` (granite-4.0-h-micro,
``model_type`` granitemoehybrid): the published model's mathematics in
straightforward ``jax.numpy``, float32 at matmul precision "highest": no
cache, no kernels, no batching, the state-space recurrence one token after
another.  It imports nothing of the program.  The sibling ``reference.py``
lends the float8 rounding, the matmul, the norm and the seed key;
``reference_nemotron3.py`` lends the state-space mixer (:func:`ssm`: the
same Mamba-2 equations, here with ONE group, so B and C are shared by all
the heads and the gated norm runs over all the channels at once) and the
state comparison's gap by head.

u is a part's normed input; every RMSNorm has eps ``rms_norm_eps`` and a
plain weight; no linear layer has a bias:

    x_0 = embedding_multiplier * E[token]
    layer l of ``layer_types``:
        h  = x + residual_multiplier * mixer_l(RMSNorm(x))
        x' = h + residual_multiplier * W_out (silu(W_g u) * W_u u),
             u = RMSNorm(h), width ``shared_intermediate_size``
             (``num_local_experts`` 0: no mixture anywhere)
    "attention": q = W_q u (``num_attention_heads`` x D), k, v = W_k u,
        W_v u (``num_key_value_heads`` x D), NO positional embedding
        (``position_embedding_type`` "nope"), scores q . k *
        ``attention_multiplier`` (NOT D ** -0.5), causal softmax, W_o
    "mamba": ``reference_nemotron3.ssm`` with n = ``mamba_n_heads``, P =
        ``mamba_d_head``, N = ``mamba_d_state``, G = ``mamba_n_groups``, K =
        ``mamba_d_conv`` taps with a bias, dt = softplus(dt~ + dt_bias)
        unclamped, A = -exp(A_log), the D skip, the gate first and then the
        RMSNorm over each group's channels
    logits = RMSNorm(x_L) E^T / logits_scaling   (``tie_word_embeddings``:
        the head IS the embedding)

``quant`` names a CONTROL, something else put in this reference's place:
``"fp8"`` rounds both operands of every linear layer to float8 e4m3 (the
recurrence stays float32); ``"residual_1"`` joins every part with factor 1;
``"attention_rsqrt"`` scales the scores by D ** -0.5; ``"untied_head"``
scores against an independently drawn head.  ``state_round`` is the state
comparison's: the state rounded to that dtype after every token.
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
HIGHEST, _mm, _rms, seed_key = (_ref.HIGHEST, _ref._mm, _ref._rms,
                                _ref.seed_key)
_nemo = _sibling("reference_nemotron3")
ssm, _head_gap = _nemo.ssm, _nemo._head_gap

CONTROLS = ("fp8", "residual_1", "attention_rsqrt", "untied_head")


def _fp8(quant):
    """What the linear layers are told of a control: float8 or nothing."""
    return quant if quant == "fp8" else None


#: the step's bias is drawn as the family's code initialises it: dt
#: log-uniform in DT_MIN .. DT_MAX, floored (the config states no such key)
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4
#: the embedding's rows are normal x EMBED_STD: the tied head scores every
#: token's row against the stream, which starts as 12 x the fed token's own
#: row; at the siblings' 0.02 that token's logit would stand 5 standard
#: deviations over the other 100 351, greedy decoding would repeat one token
#: and no rounding could flip it.  At 0.004 it stands about one over them
EMBED_STD = 0.004
#: rows of the feed-forward part computed at a time (its [T, 8192] float32
#: intermediates of a 16 896-token request are 0.55 GB each)
FFN_ROWS = 4224


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names at the top level of the file."""
    m = config
    for key, want in (("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True),
                      ("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm"),
                      ("num_local_experts", 0)):
        if m.get(key, want) != want:
            raise KeyError(f"reference_granite4 describes {key}={want!r}; "
                           f"this configuration states {m[key]!r}")
    kinds = tuple(m["layer_types"])
    if len(kinds) != m["num_hidden_layers"] or set(kinds) - {
            "mamba", "attention"}:
        raise KeyError("layer_types does not name every layer as 'mamba' or "
                       "'attention'")
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "kinds": kinds,
        "heads": m["num_attention_heads"],
        "kv_heads": m["num_key_value_heads"],
        # no key of the source: hidden_size / num_attention_heads (assumed)
        "head_dim": m.get("head_dim") or (m["hidden_size"]
                                          // m["num_attention_heads"]),
        "m_heads": m["mamba_n_heads"],
        "m_head_dim": m["mamba_d_head"],
        "groups": m["mamba_n_groups"],
        "state": m["mamba_d_state"],
        "taps": m["mamba_d_conv"],
        "inter": m["shared_intermediate_size"],
        "vocab": m["vocab_size"],
        "embed_mult": float(m["embedding_multiplier"]),
        "resid_mult": float(m["residual_multiplier"]),
        "attn_mult": float(m["attention_multiplier"]),
        "logits_div": float(m["logits_scaling"]),
        "eps": float(m["rms_norm_eps"]),
        "param_dtype": config.get("served", {}).get("param_dtype",
                                                    "bfloat16"),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict):
    """The model's weights in the tree layout the program's entry points
    take: ``embed`` (the head too: there is NO ``lm_head``), ``final_norm``,
    ``layers``, each a mixer (``attn_norm`` + a 'mamba' layer's ``ssm_win /
    ssm_conv_w / ssm_conv_b / ssm_dt_bias / ssm_A_log / ssm_D / ssm_norm /
    wo`` or an 'attention' layer's ``wq / wk / wv / wo``) and a dense part
    (``ffn_norm`` + ``moe``: ``w_gate / w_up / w_down`` stacked over ONE
    expert, zero biases, and the one-column ``gate_w`` the program's tree
    has and nothing reads).  Matrices are normal / sqrt(fan_in) (W_q and
    W_k times ``sqrt(head_dim ** -0.5 / attention_multiplier)`` each), the
    embedding normal x ``EMBED_STD``, norms one, the convolution's bias
    normal x 0.1, the step's bias softplus^-1 of a dt log-uniform in
    ``DT_MIN`` .. ``DT_MAX``, A uniform in 1 .. 16, D one.  ``seed`` may
    exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, v, i = d["hidden"], d["vocab"], d["inter"]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    n, p = d["m_heads"], d["m_head_dim"]
    di = n * p
    width = di + 2 * d["groups"] * d["state"]

    def nrm(k, shape, fan, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (gain / math.sqrt(fan))).astype(dt)

    # W_q and W_k carry what the published factor leaves out: the scores
    # q . k * attention_multiplier then spread as unit-variance projections
    # spread under head_dim ** -0.5 (a model trained under 1 / 64 where
    # others take 1 / 8 has learned the other sqrt(8) into its
    # projections; drawn without it the softmax is flat over thousands of
    # tokens, the layer adds next to nothing and NO comparison of served
    # tokens sees the factor: PERF.md section 4, PR 49)
    qk_gain = math.sqrt(dh ** -0.5 / d["attn_mult"])

    def dense_part(key):
        ks = jax.random.split(key, 4)
        return {"ffn_norm": jnp.ones((h,), dt), "moe": {
            "gate_w": nrm(ks[0], (h, 1), h),
            "w_gate": nrm(ks[1], (1, h, i), h),
            "w_up": nrm(ks[2], (1, h, i), h),
            "b_up": jnp.zeros((1, i), dt),
            "w_down": nrm(ks[3], (1, i, h), i),
            "b_down": jnp.zeros((1, h), dt)}}

    def mamba(key):
        ks = jax.random.split(key, 7)
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[3], (n,), jnp.float32, math.log(DT_MIN),
            math.log(DT_MAX))), DT_FLOOR)
        return {
            "attn_norm": jnp.ones((h,), dt),
            "ssm_win": nrm(ks[0], (h, di + width + n), h),
            "ssm_conv_w": nrm(ks[1], (d["taps"], width), d["taps"]),
            "ssm_conv_b": (0.1 * jax.random.normal(
                ks[2], (width,), jnp.float32)).astype(dt),
            "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssm_A_log": jnp.log(jax.random.uniform(
                ks[4], (n,), jnp.float32, 1.0, 16.0)),
            "ssm_D": jnp.ones((n,), jnp.float32),
            "ssm_norm": jnp.ones((di,), dt),
            "wo": nrm(ks[5], (di, h), di),
            **dense_part(ks[6])}

    def attention_layer(key):
        ks = jax.random.split(key, 5)
        return {"attn_norm": jnp.ones((h,), dt),
                "wq": nrm(ks[0], (h, nh * dh), h, qk_gain),
                "wk": nrm(ks[1], (h, nkv * dh), h, qk_gain),
                "wv": nrm(ks[2], (h, nkv * dh), h),
                "wo": nrm(ks[3], (nh * dh, h), nh * dh),
                **dense_part(ks[4])}

    make = {"mamba": jax.jit(mamba), "attention": jax.jit(attention_layer)}

    @jax.jit
    def ends(key):
        return {"embed": (jax.random.normal(key, (v, h), jnp.float32)
                          * EMBED_STD).astype(dt),
                "final_norm": jnp.ones((h,), dt)}

    params = ends(seed_key(seed, 0))
    params["layers"] = [make[kind](seed_key(seed, 1 + li))
                        for li, kind in enumerate(d["kinds"])]
    return params


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def attention(layer, x, d, quant=None, q_block=512):
    """Causal grouped-query attention with no positional embedding, over
    one sequence x: [T, H] float32 (already normed), scores times
    ``attention_multiplier``, in blocks of ``q_block`` rows."""
    t = x.shape[0]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    scale = dh ** -0.5 if quant == "attention_rsqrt" else d["attn_mult"]
    mm = _fp8(quant)
    pos = jnp.arange(t)
    q = _mm(x, layer["wq"], mm).reshape(t, nh, dh)
    k = _mm(x, layer["wk"], mm).reshape(t, nkv, dh)
    v = _mm(x, layer["wv"], mm).reshape(t, nkv, dh)
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))

    def rows(qb, pb):
        s = jnp.einsum("tnd,snd->nts", qb, k, precision=HIGHEST) * scale
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
    return _mm(ctx.reshape(t, nh * dh), layer["wo"], mm)


def dense(p, x, quant=None):
    """The feed-forward part over x: [T, H] float32 (normed):
    W_out (silu(W_g x) * W_u x), ``FFN_ROWS`` rows at a time."""
    mm = _fp8(quant)

    def rows(xb):
        hidden = jax.nn.silu(_mm(xb, p["w_gate"][0], mm)) \
            * _mm(xb, p["w_up"][0], mm)
        return _mm(hidden, p["w_down"][0], mm)

    t = x.shape[0]
    if t <= FFN_ROWS or t % FFN_ROWS:
        return rows(x)
    return jax.lax.map(rows, x.reshape(t // FFN_ROWS, FFN_ROWS, -1)
                       ).reshape(t, -1)


def _dims_key(d):
    return tuple(sorted(d.items()))


def _layer(layer, x, d, kind, quant=None, **state):
    """(the layer's output, the state its mixer ends on or None): x is NOT
    normed yet; both parts join times ``residual_multiplier``."""
    m = 1.0 if quant == "residual_1" else d["resid_mult"]
    u = _rms(x, layer["attn_norm"], d["eps"])
    if kind == "mamba":
        a, s = ssm(layer, u, d, _fp8(quant), **state)
    else:
        a, s = attention(layer, u, d, quant), None
    x = x + m * a
    return x + m * dense(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]),
                         quant), s


@functools.partial(jax.jit, static_argnames=("dkey", "kind", "quant"))
def _block(layer, x, dkey, kind, quant):
    return _layer(layer, x, dict(dkey), kind, quant)[0]


@functools.partial(jax.jit,
                   static_argnames=("dkey", "kind", "state_round"))
def _block_state(layer, x, n_valid, dkey, kind, state_round):
    """:func:`_block` that also hands out the state after ``n_valid``
    tokens (None for an attention layer)."""
    state = (dict(n_valid=n_valid, state_round=state_round)
             if kind == "mamba" else {})
    return _layer(layer, x, dict(dkey), kind, **state)


@functools.partial(jax.jit, static_argnames=("dkey",))
def _embedded(embed, tokens, dkey):
    return dict(dkey)["embed_mult"] * embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dkey", "quant"))
def _head(final_norm, embed, x, rows, dkey, quant):
    d = dict(dkey)
    h = _rms(x[rows], final_norm, d["eps"])
    if quant == "untied_head":
        # a head of its own, drawn as the siblings draw theirs
        w = jax.random.normal(jax.random.key(7, impl="rbg"),
                              embed.shape[::-1], jnp.float32) \
            / math.sqrt(embed.shape[1])
        return jnp.dot(h, w, precision=HIGHEST) / d["logits_div"]
    return _mm(h, embed.T, _fp8(quant)) / d["logits_div"]


def forward_logits(params, d, tokens, rows, quant=None):
    """Reference logits of ONE sequence.  tokens: [T] int32 (padded past
    the true end: causality keeps pads out of earlier rows); rows: [R]
    int32 positions whose logits are wanted; ``quant``: None or one of
    ``CONTROLS``.  Layer by layer, so only one layer's float32 copies live
    at a time.  Returns [R, V] float32."""
    if quant not in (None, *CONTROLS):
        raise ValueError(f"control {quant!r} not of {CONTROLS}")
    dkey = _dims_key(d)
    x = _embedded(params["embed"], tokens, dkey)
    for layer, kind in zip(params["layers"], d["kinds"]):
        x = _block(layer, x, dkey, kind, quant)
    return _head(params["final_norm"], params["embed"], x, rows, dkey,
                 quant)


# ----------------------------------------------------------------------
# the served-model comparison (the siblings', over this forward pass)
# ----------------------------------------------------------------------

def served_token_gaps(params, d, streams, t_pad, r_pad, control=None):
    """For each served stream ``(prompt, served_tokens)``: run the
    reference once over prompt + served tokens and read, at every served
    position, how far the served token's logit lies below the reference's
    best, as a share of the largest logit magnitude among the compared
    rows.  With ``control`` (a name of ``CONTROLS``) the token read at each
    position is instead the one the control puts first.  Returns
    ``{"widest", "mean", "tokens", "per_stream"}``."""
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


# ----------------------------------------------------------------------
# the state comparison (``reference_nemotron3.state_gaps``'s contract)
# ----------------------------------------------------------------------

def final_states(params, d, tokens, n_valid, layers, state_round=None):
    """The state [n, P, N] of every 'mamba' layer among the first
    ``layers`` layers after ``n_valid`` tokens of ONE sequence.  tokens:
    [T] int32, padded past ``n_valid`` (a pad leaves every state alone)."""
    dkey, out = _dims_key(d), []
    x = _embedded(params["embed"], tokens, dkey)
    for li in range(layers):
        x, s = _block_state(params["layers"][li], x, n_valid, dkey,
                            d["kinds"][li], state_round)
        if s is not None:
            out.append(s)
    return out


def state_gaps(params, d, streams, t_pad, layers=1, control=None):
    """For each ``(tokens, states)`` (the tokens a slot of the TIMED engine
    has consumed, and the float32 state ``[n_states, n, P, N]`` the 'mamba'
    layers among its first ``layers`` layers then held): the reference's
    recurrence over the same tokens, and per state layer the gap of the
    HEAD that differs most, ``max_h |S_h - S_ref,h| / |S_ref,h|``
    (``reference_nemotron3.state_gaps`` says why by head, and why layer 0,
    whose inputs are embedding rows on both sides, is the one judged).
    With ``control`` (a dtype name) the state read is instead the
    reference's own with the state rounded to that dtype after every token.
    Returns ``{"widest": layer 0's largest gap over the streams,
    "per_stream": [[gap per state layer]]}``."""
    per = []
    for tokens, states in streams:
        toks = np.zeros((t_pad,), np.int32)
        toks[:len(tokens)] = tokens
        args = (params, d, jnp.asarray(toks), len(tokens), layers)
        ref = [np.asarray(s) for s in final_states(*args)]
        got = (states if control is None else
               [np.asarray(s) for s in final_states(
                   *args, state_round=control)])
        per.append([_head_gap(g, r) for g, r in zip(got, ref)])
    return {"widest": max((p[0] for p in per), default=float("nan")),
            "per_stream": per}
