"""The plain reference of the state-space hybrids (``nemotron3_nano``:
NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type`` nemotron_h): the published
block's mathematics in straightforward ``jax.numpy``, float32 at matmul
precision "highest": the state-space recurrence as itself, one token after
another (a ``lax.scan``: no chunked form), grouped-query attention over the
whole sequence, no cache, no kernels, no batching, every expert held here
evaluated on every token (one at a time, so that 4 608 tokens fit beside
the served weights).  It imports nothing of the program; the sibling
``reference.py`` lends the float8 rounding, the matmul, the norm and the
seed key.

A block is ONE thing behind ONE norm (RMSNorm with a weight, eps
``layer_norm_epsilon``):  x <- x + f_l(norm(x; w_l)), f_l by the letter l of
``hybrid_override_pattern``; after the last a final RMSNorm and an UNTIED
head.  No bias in any linear layer.

``M``, Mamba-2; u the normed input, n = ``mamba_num_heads`` heads of P =
``mamba_head_dim``, G = ``n_groups`` groups of N = ``ssm_state_size``, K =
``conv_kernel`` taps, head h reads group h // (n / G):
    [z | xBC | dt~] = u W_in  (H -> n P | n P + 2 G N | n: d_inner is
        heads x head width, NOT ``expand`` x H: assumed, as the family's
        code sizes it)
    xBC_t <- silu(sum_{j < K} w[j] xBC_{t-K+1+j} + b)   (depthwise, causal,
        zeros before the sequence's start, with a bias)
    [x_t (n, P) | B_t (G, N) | C_t (G, N)] = xBC_t
    dt_t,h = softplus(dt~_t,h + dt_bias_h)  (NO clamp: the step limits
        default to (0, inf); ``time_step_*`` only initialise dt_bias)
    A_h = -exp(A_log_h);   S_h in float32 [P, N]:
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t,h (x) B_t,g(h)
    y_t,h = S_t C_t,g(h) + D_h x_t,h
    out = W_out [RMSNorm_group(y * silu(z)) * w]   (the gate first, then
        the norm over each group of n P / G channels: assumed order and
        group size, as the family's gated norm with ``n_groups`` groups)
``*``, attention: q [N_q, D], k and v [N_kv, D] from u; causal softmax of
    q k^T / sqrt(D), N_q / N_kv query heads a K/V head; NO rotary embedding
    (assumed: the family's attention applies none); o_proj.
``E``, mixture: s = sigmoid(u W_g) in float32 over ALL published experts;
    the ``num_experts_per_tok`` experts with the largest s + b (b the
    ``e_score_correction_bias``, for the CHOICE only; ``n_group`` 1: the
    plain rule); weights s_e / (sum of the chosen + 1e-20)
    (``norm_topk_prob``) x ``routed_scaling_factor``; expert e:
    W_down,e relu(W_up,e u)^2, no gate matrix; one shared expert of the
    same form, added unweighted.  THIS CHIP'S SHARE: the weights of experts
    ``expert_first`` .. + ``experts`` - 1 are here; what the absent experts
    would have added is left out, and that partial result goes on to the
    next layer (the program does the same).  The vocabulary is a slice.

``quant="fp8"`` is the CONTROL: both operands of every linear layer
rounded to float8 e4m3 (the sibling's ``_mm``); the recurrence itself stays
float32.  ``state_round`` is the state comparison's: the state rounded to
that dtype after every token.
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
# the sigmoid router's scores and the fit of its selection bias are the
# gated-convolution sibling's, to the letter
_lfm2 = _sibling("reference_lfm2")
router_scores, _fitted_bias = _lfm2.router_scores, _lfm2._fitted_bias

#: the selection bias is FITTED so that the experts' loads balance, as the
#: siblings' (``reference_lfm2``'s reason, its steps and rates): by the
#: checkpoint's own update rule on PROBE_TOKENS tokens drawn from the seed
PROBE_TOKENS = _lfm2.PROBE_TOKENS


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names at the top level of the file; ``published``
    gives the router's width, ``held`` the share of the experts here."""
    m = config
    for key, want in (("use_conv_bias", True), ("mamba_proj_bias", False),
                      ("mlp_bias", False), ("attention_bias", False),
                      ("use_bias", False), ("n_group", 1),
                      ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("residual_in_fp32", False)):
        if m.get(key, want) != want:
            raise KeyError(f"reference_nemotron3 describes {key}={want!r}; "
                           f"this configuration states {m[key]!r}")
    pattern = m["hybrid_override_pattern"]
    if len(pattern) != m["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise KeyError("hybrid_override_pattern does not name every layer "
                       "as M, E or *")
    held = config.get("held", {})
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "kinds": tuple(pattern),
        "heads": m["num_attention_heads"],
        "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"],
        "m_heads": m["mamba_num_heads"],
        "m_head_dim": m["mamba_head_dim"],
        "groups": m["n_groups"],
        "state": m["ssm_state_size"],
        "taps": m["conv_kernel"],
        "vocab": m["vocab_size"],
        "router_experts": config.get("published", {}).get(
            "n_routed_experts", m["n_routed_experts"]),
        "experts": m["n_routed_experts"],
        "expert_first": held.get("expert_first", 0),
        "top_k": m["num_experts_per_tok"],
        "inter": m["moe_intermediate_size"],
        "inter_stored": config.get("served", {}).get(
            "expert_width_stored", m["moe_intermediate_size"]),
        "shared_inter": (m["moe_shared_expert_intermediate_size"]
                         * m["n_shared_experts"]),
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "eps": float(m["layer_norm_epsilon"]),
        "dt_min": float(m["time_step_min"]),
        "dt_max": float(m["time_step_max"]),
        "dt_floor": float(m["time_step_floor"]),
        "param_dtype": config.get("served", {}).get(
            "param_dtype", m.get("torch_dtype", "bfloat16")),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict):
    """The model's weights in the tree layout the program's entry points
    take (``embed``, ``final_norm``, ``lm_head``, ``layers``): an ``M``
    layer's ``attn_norm / ssm_win / ssm_conv_w / ssm_conv_b / ssm_dt_bias /
    ssm_A_log / ssm_D / ssm_norm / wo``, a ``*`` layer's ``attn_norm / wq /
    wk / wv / wo``, an ``E`` layer's ``ffn_norm / moe`` (the router over ALL
    experts ``gate_w``, the selection bias ``gate_bias`` float32, fitted:
    :func:`balance_biases`; the stacked weights of the experts HELD,
    ``w_up / w_down`` with zero biases, stored ``inter_stored`` wide with
    zeros beyond the published width; the shared expert ``shared_w_up /
    shared_w_down``).  The step's bias is drawn as the family initialises
    it (dt log-uniform in ``time_step_min`` .. ``time_step_max``, floored,
    through softplus^-1), the rates A uniform in 1 .. 16, D one: a head's
    usual decay then lies anywhere between exp(-0.001) and exp(-1.6) a
    token.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, v = d["hidden"], d["vocab"]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    n, p = d["m_heads"], d["m_head_dim"]
    di = n * p
    width = di + 2 * d["groups"] * d["state"]

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    def mixer_m(key):
        ks = jax.random.split(key, 6)
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[3], (n,), jnp.float32, math.log(d["dt_min"]),
            math.log(d["dt_max"]))), d["dt_floor"])
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
            "wo": nrm(ks[5], (di, h), di)}

    def mixer_a(key):
        ks = jax.random.split(key, 4)
        return {"attn_norm": jnp.ones((h,), dt),
                "wq": nrm(ks[0], (h, nh * dh), h),
                "wk": nrm(ks[1], (h, nkv * dh), h),
                "wv": nrm(ks[2], (h, nkv * dh), h),
                "wo": nrm(ks[3], (nh * dh, h), nh * dh)}

    def mixture(key):
        ks = jax.random.split(key, 5)
        e, i, si = d["experts"], d["inter"], d["shared_inter"]
        # stored with zero columns (rows of w_down) up to ``inter_stored``:
        # relu(0)^2 = 0, the layer is the published one
        pad = d["inter_stored"] - i
        return {"ffn_norm": jnp.ones((h,), dt), "moe": {
            "gate_w": nrm(ks[0], (h, d["router_experts"]), h),
            "gate_bias": jnp.zeros((d["router_experts"],), jnp.float32),
            "w_up": jnp.pad(nrm(ks[1], (e, h, i), h),
                            [(0, 0), (0, 0), (0, pad)]),
            "b_up": jnp.zeros((e, i + pad), dt),
            "w_down": jnp.pad(nrm(ks[2], (e, i, h), i),
                              [(0, 0), (0, pad), (0, 0)]),
            "b_down": jnp.zeros((e, h), dt),
            "shared_w_up": nrm(ks[3], (h, si), h),
            "shared_w_down": nrm(ks[4], (si, h), si)}}

    make = {"M": jax.jit(mixer_m), "*": jax.jit(mixer_a),
            "E": jax.jit(mixture)}

    @jax.jit
    def ends(key):
        k0, k1 = jax.random.split(key)
        return {"embed": (jax.random.normal(k0, (v, h), jnp.float32)
                          * 0.02).astype(dt),
                "final_norm": jnp.ones((h,), dt),
                "lm_head": nrm(k1, (h, v), h)}

    params = ends(seed_key(seed, 0))
    params["layers"] = [make[kind](seed_key(seed, 1 + li))
                        for li, kind in enumerate(d["kinds"])]
    return balance_biases(params, d, seed)


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def ssm(layer, x, d, quant=None, n_valid=None, state_round=None):
    """The state-space layer over one sequence x: [T, H] float32 (already
    normed), as the recurrence: one token after another.  Returns the
    layer's output [T, H] and the state [n, P, N] after the last token, or
    after token ``n_valid - 1`` where that is given (a later position then
    has dt 0: decay 1, input 0, the state as it was; its own output is not
    to be read).  ``state_round`` names the dtype the state is rounded to
    after every token: the CONTROL of the state comparison."""
    t = x.shape[0]
    n, p, g, ns, taps = (d["m_heads"], d["m_head_dim"], d["groups"],
                         d["state"], d["taps"])
    di = n * p
    width = di + 2 * g * ns
    u = _mm(x, layer["ssm_win"], quant)
    z, xbc, dt = u[:, :di], u[:, di:di + width], u[:, di + width:]
    full = jnp.concatenate([jnp.zeros((taps - 1, width), xbc.dtype), xbc])
    w = layer["ssm_conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(full[j:j + t] * w[j] for j in range(taps))
                      + layer["ssm_conv_b"].astype(jnp.float32))
    xs = xbc[:, :di].reshape(t, n, p)
    heads_of = lambda m: jnp.repeat(m.reshape(t, g, ns), n // g, axis=1)
    bm, cm = heads_of(xbc[:, di:di + g * ns]), heads_of(xbc[:, di + g * ns:])
    dt = jax.nn.softplus(dt + layer["ssm_dt_bias"].astype(jnp.float32))
    if n_valid is not None:
        dt = jnp.where((jnp.arange(t) < n_valid)[:, None], dt, 0.0)
    a = -jnp.exp(layer["ssm_A_log"].astype(jnp.float32))
    info = None if state_round is None else jnp.finfo(state_round)

    def token(s, xs_t):
        x_t, b_t, c_t, dt_t = xs_t
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if state_round is not None:
            # not a pair of converts: the chip's compiler may keep the
            # excess precision of those, and the control then reads 0
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s, y = jax.lax.scan(token, jnp.zeros((n, p, ns), jnp.float32),
                        (xs, bm, cm, dt))
    y = y + layer["ssm_D"].astype(jnp.float32)[:, None] * xs
    y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + d["eps"])
    y = y.reshape(t, di) * layer["ssm_norm"].astype(jnp.float32)
    return _mm(y, layer["wo"], quant), s


def attention(layer, x, d, quant=None, q_block=512):
    """Causal grouped-query attention with no positional embedding, over
    one sequence x: [T, H] float32 (already normed), the scores in blocks
    of ``q_block`` rows."""
    t = x.shape[0]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(t)
    q = _mm(x, layer["wq"], quant).reshape(t, nh, dh)
    k = _mm(x, layer["wk"], quant).reshape(t, nkv, dh)
    v = _mm(x, layer["wv"], quant).reshape(t, nkv, dh)
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


def router_weights(x, gate_w, gate_bias, d):
    """[T, E] dense combine weights over ALL experts and the chosen
    experts [T, k]: sigmoid scores; the choice is the top-k of score +
    bias; the chosen scores themselves (WITHOUT the bias) normalised and
    scaled."""
    s = router_scores(x, gate_w)
    top_i = jax.lax.top_k(s + gate_bias.astype(jnp.float32)[None, :],
                          d["top_k"])[1]
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * d["scaling"]
    cw = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, gate_w.shape[1], dtype=jnp.float32))
    return cw, top_i


def _relu2(x, w_up, w_down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, quant))), w_down, quant)


def ffn(p, x, d, quant=None, shared=True):
    """The mixture of one layer over x: [T, H] float32 (normed): every
    expert HELD here on every token, one at a time, combined through its
    column of the dense weight matrix, plus (``shared``) the shared
    expert."""
    cw, _ = router_weights(x, p["gate_w"], p["gate_bias"], d)

    def one(acc, e):
        y = _relu2(x, p["w_up"][e], p["w_down"][e], quant)
        return acc + cw[:, d["expert_first"] + e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(p["w_up"].shape[0]))
    if shared:
        out = out + _relu2(x, p["shared_w_up"], p["shared_w_down"], quant)
    return out


def _dims_key(d):
    return tuple(sorted(d.items()))


def _part(layer, x, d, kind, quant=None, **state):
    """(what the layer adds to x, the state it ends on or None): x is NOT
    normed yet; the layer's one norm is applied here."""
    if kind == "E":
        return ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]), d,
                   quant), None
    h = _rms(x, layer["attn_norm"], d["eps"])
    if kind == "M":
        return ssm(layer, h, d, quant, **state)
    return attention(layer, h, d, quant), None


@functools.partial(jax.jit, static_argnames=("dkey", "kind", "quant"))
def _block(layer, x, dkey, kind, quant):
    return x + _part(layer, x, dict(dkey), kind, quant)[0]


@functools.partial(jax.jit,
                   static_argnames=("dkey", "kind", "state_round"))
def _block_state(layer, x, n_valid, dkey, kind, state_round):
    """:func:`_block` that also hands out the state after ``n_valid``
    tokens (None for a layer that keeps none)."""
    state = (dict(n_valid=n_valid, state_round=state_round)
             if kind == "M" else {})
    y, s = _part(layer, x, dict(dkey), kind, **state)
    return x + y, s


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head, x, rows, eps, quant):
    return _mm(_rms(x[rows], final_norm, eps), lm_head, quant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, eps):
    return _rms(x, w, eps)


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
        if kind == "E":
            layer["moe"]["gate_bias"] = _fitted_bias(
                _normed(x, layer["ffn_norm"], d["eps"]),
                layer["moe"]["gate_w"], dkey)
        x = _block(layer, x, dkey, kind, None)
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


# ----------------------------------------------------------------------
# the state comparison
# ----------------------------------------------------------------------

def final_states(params, d, tokens, n_valid, layers, state_round=None):
    """The state [n, P, N] of every ``M`` layer among the first ``layers``
    layers after ``n_valid`` tokens of ONE sequence.  tokens: [T] int32,
    padded past ``n_valid`` (a pad leaves every state alone)."""
    x = params["embed"][tokens].astype(jnp.float32)
    dkey, out = _dims_key(d), []
    for li in range(layers):
        x, s = _block_state(params["layers"][li], x, n_valid, dkey,
                            d["kinds"][li], state_round)
        if s is not None:
            out.append(s)
    return out


def _head_gap(got, want):
    """The largest over the heads of ``|S_h - S_ref,h| / |S_ref,h|``
    (Frobenius, a head's [P, N] block)."""
    diff = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    norm = lambda a: np.sqrt((a.reshape(a.shape[0], -1) ** 2).sum(-1))
    return float((norm(diff) / norm(np.asarray(want, np.float64))).max())


def state_gaps(params, d, streams, t_pad, layers=1, control=None):
    """For each ``(tokens, states)`` (the tokens a slot of the TIMED engine
    has consumed, and the float32 state ``[n_states, n, P, N]`` the ``M``
    layers among its first ``layers`` layers then held): the reference's
    recurrence over the same tokens, and per state layer the gap of the
    HEAD that differs most, ``max_h |S_h - S_ref,h| / |S_ref,h|``.

    Why by head, where the delta-rule sibling takes the whole state: a
    state kept or decayed in a lower precision than float32 adds a
    rounding of the WHOLE state every token, which sums over a head's
    memory, and the heads here differ in memory by a factor of a thousand
    (dt x |A| from 0.002 to 1.6 a token).  The slowest heads show it (a
    state rounded to bfloat16 after every token moves them by 3-12 % of
    their norm), while the whole state's norm belongs to the fastest
    heads' large entries, which a rounding hardly moves: over the whole
    state a sound run read 0.0046-0.0068 and the bfloat16 control 0.0049,
    0.0084 and 0.040 on the chip: no limit between them (PERF.md section
    6, PR 39).  What layer 0's gap reads (the pattern's first letter is
    ``M``): its inputs are embedding rows, the same numbers on both
    sides, so the gap is the mixer's own arithmetic (the projection's
    bfloat16 output: under 1 % of any head) and NOT what earlier layers'
    roundings and routing flips added (``reference_ling3.state_gaps``'s
    argument).  With ``control`` (a dtype name) the state read is instead
    the reference's own with the state rounded to that dtype after every
    token.  Returns ``{"widest": layer 0's largest gap over the streams,
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
