"""The plain reference of the hybrid linear-attention configurations
(``ling3_flash``: the language model of Ling-3.0-flash-VL): the published
block's mathematics in straightforward ``jax.numpy``, float32 at matmul
precision "highest": the delta rule as the plain recurrence, one token
after another (a ``lax.scan``), latent attention in the decompressing form
over the whole sequence, no cache, no chunks, no batching, every expert
held here evaluated on every token (one at a time, so that 10 240 tokens
fit beside the served weights).  It imports nothing of the program; the
siblings lend the float8 rounding, the matmul, the norm, the seed key
(``reference.py``) and the adjacent-pair RoPE (``reference_mla.py``).

Pre-norm residual blocks, RMSNorm eps 1e-6:  x += mixer_l(norm(x));
x += ffn_l(norm(x)); final RMSNorm, untied head.  The configuration file
names the kind of every layer (``layer_kinds``).

KDA layer, per token t, N heads of d_k = d_v = D, no positions:
    [q~ | k~ | v~] = x W_qkv; each channel through a causal depthwise
    convolution of K taps over time (y_t = sum_j w_j u_{t-K+1+j}), then SiLU
    q = l2norm(q'), k = l2norm(k') per head (x / sqrt(sum x^2 + 1e-6))
    log alpha_t = L * sigmoid(exp(A_n) * (x W_a + b)),  L = kda_lower_bound,
        one decay a head and CHANNEL (the bounded "safe" gate: ASSUMED form)
    beta_t = sigmoid(x W_b), one a head
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
        S in R^{D x D} a head, float32: decay the rows, then
        S += beta_t k_t (v_t - S^T k_t)^T
    o_t = D^-1/2 S_t^T q_t;  out = W_o [RMSNorm_head(o_t) * sigmoid(g_t)],
        g_t = x W_g one gate a head (head_wise; ASSUMED to name this gate)
MLA layer: ``reference_mla``'s with q = x W_q direct (``q_lora_rank`` null):
    [c | k_r] = x W_kva; c_kv = RMSNorm(c); k_rope = RoPE(k_r), one key for all
    heads; [k_nope_h | v_h] = c_kv W_kvb; [q_nope_h | q_rope_h] = x W_q;
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + RoPE(q_rope_h)(t).k_rope(s))
    / sqrt(d_nope + d_rope), causal softmax.  RoPE rotates ADJACENT pairs.
    ``use_qk_norm`` is ASSUMED to add nothing beyond the latent's norm.
Router: s = sigmoid(x W_r) in float32 over ALL published experts; c = s +
    bias; the experts lie in ``n_group`` consecutive groups, a group scores
    the sum of its two largest c, the ``topk_group`` best groups are kept;
    the experts are the top-k of c inside them; their weights are s_i /
    (sum of the chosen + 1e-20) * scaling.
Experts: SwiGLU.  THIS CHIP'S SHARE: the weights of experts ``expert_first``
    .. + ``experts`` - 1 are here; y = sum over the chosen experts that are
    held here of w_i E_i(x), + E_shared(x).  What the absent experts would
    have added is left out, and that partial result goes on to the next
    layer (the program does the same).  The vocabulary is a slice: ids,
    logits and argmax are over it.

``quant="fp8"`` is the CONTROL: both operands of every linear layer
rounded to float8 e4m3 (the sibling's ``_mm``); the recurrence itself
stays float32.
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
rope_adjacent = _sibling("reference_mla").rope_adjacent

#: the selection bias is what BALANCES the experts' load in a checkpoint
#: (its update rule: after a batch, raise the bias of an expert that got
#: fewer rows than the mean by a fixed step, lower it otherwise).  With
#: weights from a seed there is much to balance: half of a hidden state's
#: norm is a direction all tokens share (q, k and v come out of a SiLU, so
#: the delta rule's outputs have a common sign), which gives every expert
#: a constant offset of some 0.1 in its score, another under every seed;
#: unbalanced, a decode step touched 46-51 of the 128 experts held where a
#: balanced router touches 80, this chip's share of the rows spread by a
#: third, and the cell's speed followed the seed (PERF.md section 6).  So
#: the bias is fitted by that rule on PROBE_TOKENS tokens drawn from the
#: seed: BALANCE_STEPS steps of each of BALANCE_RATES, the last one the
#: published step size
PROBE_TOKENS, BALANCE_STEPS, BALANCE_RATES = 2048, 120, (0.01, 0.003, 0.001)


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names, at the top level of the file or under
    ``model``; the published expert count under ``published``, the share
    held under ``held``, the layers' kinds under ``layer_kinds``."""
    m = config.get("model", config)
    if m.get("score_function", m.get("scoring_func")) != "sigmoid" \
            or m.get("q_lora_rank") is not None:
        raise KeyError("reference_ling3 describes a sigmoid router and "
                       "latent attention without a query rank; this "
                       "configuration states another")
    kinds = tuple(config["layer_kinds"])
    if len(kinds) != m["num_hidden_layers"]:
        raise KeyError("layer_kinds does not name every layer")
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "kinds": kinds,
        "heads": m["num_attention_heads"],
        "kv_rank": m["kv_lora_rank"],
        "d_nope": m["qk_nope_head_dim"],
        "d_rope": m["qk_rope_head_dim"],
        "d_v": m["v_head_dim"],
        "kda_dim": m["head_dim"],
        "conv": m["short_conv_kernel_size"],
        "lower": float(m["kda_lower_bound"]),
        "vocab": m["vocab_size"],
        "router_experts": config.get("published", m)["num_experts"],
        "experts": m["num_experts"],
        "expert_first": config.get("held", {}).get("expert_first", 0),
        "top_k": m["num_experts_per_tok"],
        "n_group": m["n_group"],
        "topk_group": m["topk_group"],
        "shared": config.get("assumed_sizes", {}).get(
            "num_shared_experts", 1),
        "inter": m["moe_intermediate_size"],
        "dense_inter": m["intermediate_size"],
        "first_dense": m["first_k_dense_replace"],
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "rope_theta": float(m["rope_theta"]),
        "eps": float(m.get("rms_norm_eps", 1e-6)),
        "param_dtype": config.get("served", {}).get(
            "param_dtype", m.get("torch_dtype", "bfloat16")),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict):
    """The model's weights in the tree layout the program's entry points
    take (``embed``, ``final_norm``, ``lm_head``, ``layers`` of
    ``attn_norm / ffn_norm / moe`` and a mixer: a 'kda' layer's
    ``kda_wqkv / kda_conv / kda_wa / kda_A / kda_b / kda_wb / kda_wg /
    kda_norm / wo``, an 'mla' layer's ``wq / wkv_a / kv_a_norm / wkv_b /
    wo``).  A mixture layer's ``moe`` holds the router over ALL experts
    (``gate_w``, the selection bias ``gate_bias`` float32, fitted so that
    the experts' loads balance: :func:`balance_biases`)
    and the stacked weights of the experts HELD; a dense layer's holds one
    expert of the dense width.  The decay's rate ``kda_A`` (a head) and
    offset ``kda_b`` (a channel) are drawn so that a channel's usual decay
    lies anywhere between exp(-0.01) and exp(-4.4) a token: memories of a
    hundred tokens beside memories of one.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, v = d["hidden"], d["heads"], d["vocab"]
    rkv, dn, dr, dv = d["kv_rank"], d["d_nope"], d["d_rope"], d["d_v"]
    dk, taps = d["kda_dim"], d["conv"]

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    def ffn(key, n_route, n_exp, inter, n_shared):
        ks = jax.random.split(key, 8)
        p = {"gate_w": nrm(ks[0], (h, n_route), h),
             "w_up": nrm(ks[1], (n_exp, h, inter), h),
             "b_up": jnp.zeros((n_exp, inter), dt),
             "w_down": nrm(ks[2], (n_exp, inter, h), inter),
             "b_down": jnp.zeros((n_exp, h), dt),
             "w_gate": nrm(ks[3], (n_exp, h, inter), h)}
        if n_route > 1:
            p["gate_bias"] = jnp.zeros((n_route,), jnp.float32)  # fitted below
        if n_shared:
            si = inter * n_shared
            p["shared_w_up"] = nrm(ks[4], (h, si), h)
            p["shared_w_down"] = nrm(ks[5], (si, h), si)
            p["shared_w_gate"] = nrm(ks[6], (h, si), h)
        return p

    def mixer(key, kind):
        ks = jax.random.split(key, 9)
        if kind == "kda":
            return {
                "kda_wqkv": nrm(ks[0], (h, 3 * nh * dk), h),
                "kda_conv": nrm(ks[1], (taps, 3 * nh * dk), taps),
                "kda_wa": nrm(ks[2], (h, nh * dk), h),
                "kda_A": jax.random.uniform(ks[3], (nh,), jnp.float32,
                                            -0.5, 0.5),
                "kda_b": jax.random.uniform(ks[4], (nh * dk,), jnp.float32,
                                            -6.0, 2.0),
                "kda_wb": nrm(ks[5], (h, nh), h),
                "kda_wg": nrm(ks[6], (h, nh), h),
                "kda_norm": jnp.ones((dk,), dt),
                "wo": nrm(ks[7], (nh * dk, h), nh * dk)}
        return {"wq": nrm(ks[0], (h, nh * (dn + dr)), h),
                "wkv_a": nrm(ks[1], (h, rkv + dr), h),
                "kv_a_norm": jnp.ones((rkv,), dt),
                "wkv_b": nrm(ks[2], (rkv, nh * (dn + dv)), rkv),
                "wo": nrm(ks[3], (nh * dv, h), nh * dv)}

    @functools.partial(jax.jit, static_argnames=("dense", "kind"))
    def layer(key, dense, kind):
        k0, k1 = jax.random.split(key)
        return {
            "attn_norm": jnp.ones((h,), dt), "ffn_norm": jnp.ones((h,), dt),
            **mixer(k0, kind),
            "moe": (ffn(k1, 1, 1, d["dense_inter"], 0) if dense
                    else ffn(k1, d["router_experts"], d["experts"],
                             d["inter"], d["shared"])),
        }

    @jax.jit
    def ends(key):
        k0, k1 = jax.random.split(key)
        return {"embed": (jax.random.normal(k0, (v, h), jnp.float32)
                          * 0.02).astype(dt),
                "final_norm": jnp.ones((h,), dt),
                "lm_head": nrm(k1, (h, v), h)}

    params = ends(seed_key(seed, 0))
    params["layers"] = [layer(seed_key(seed, 1 + li),
                              dense=li < d["first_dense"],
                              kind=d["kinds"][li])
                        for li in range(d["layers"])]
    return balance_biases(params, d, seed)


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(layer, x, d, quant=None, n_valid=None, state_round=None):
    """The delta-rule layer over one sequence x: [T, H] float32 (already
    normed), as the recurrence: one token after another.  Returns the
    layer's output [T, H] and the state [N, D, D] after the last token, or
    after token ``n_valid - 1`` where that is given (a later position then
    has decay 1 and beta 0: it leaves the state as it was; its own output
    is not to be read).  ``state_round`` names the dtype the state is
    rounded to after every token: the CONTROL of the state comparison."""
    t = x.shape[0]
    n, dk, taps = d["heads"], d["kda_dim"], d["conv"]
    u = _mm(x, layer["kda_wqkv"], quant)                     # [T, 3 N D]
    full = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    w = layer["kda_conv"].astype(jnp.float32)
    y = jax.nn.silu(sum(full[j:j + t] * w[j] for j in range(taps)))
    q, k, v = (y[:, i * n * dk:(i + 1) * n * dk].reshape(t, n, dk)
               for i in range(3))
    q, k = _l2norm(q), _l2norm(k)
    a = (_mm(x, layer["kda_wa"], quant)
         + layer["kda_b"].astype(jnp.float32)).reshape(t, n, dk)
    alpha = jnp.exp(d["lower"] * jax.nn.sigmoid(
        jnp.exp(layer["kda_A"].astype(jnp.float32))[None, :, None] * a))
    beta = jax.nn.sigmoid(_mm(x, layer["kda_wb"], quant))    # [T, N]
    gate = jax.nn.sigmoid(_mm(x, layer["kda_wg"], quant))
    if n_valid is not None:
        live = jnp.arange(t) < n_valid
        alpha = jnp.where(live[:, None, None], alpha, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)

    info = None if state_round is None else jnp.finfo(state_round)

    def token(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, :, None] * s                              # decay the rows
        seen = jnp.einsum("nkv,nk->nv", s, k_t, precision=HIGHEST)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        if state_round is not None:
            # not a pair of converts: the chip's compiler may keep the
            # excess precision of those, and the control then reads 0
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.einsum("nkv,nk->nv", s, q_t, precision=HIGHEST)

    s, o = jax.lax.scan(token, jnp.zeros((n, dk, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    o = _rms(o / math.sqrt(dk), layer["kda_norm"], d["eps"]) \
        * gate[:, :, None]
    return _mm(o.reshape(t, n * dk), layer["wo"], quant), s


def mla(layer, x, d, quant=None, q_block=512):
    """Causal multi-head latent attention with queries projected direct,
    over one sequence x: [T, H] float32 (already normed), the scores in
    blocks of ``q_block`` rows."""
    t = x.shape[0]
    nh, dn, dr, dv = d["heads"], d["d_nope"], d["d_rope"], d["d_v"]
    rkv = d["kv_rank"]
    pos = jnp.arange(t)
    q = _mm(x, layer["wq"], quant).reshape(t, nh, dn + dr)
    q_nope = q[..., :dn]
    q_rope = rope_adjacent(q[..., dn:], pos, d["rope_theta"])
    kv = _mm(x, layer["wkv_a"], quant)                       # [T, rkv + dr]
    c_kv = _rms(kv[:, :rkv], layer["kv_a_norm"], d["eps"])
    k_rope = rope_adjacent(kv[:, rkv:], pos, d["rope_theta"])   # [T, dr]
    kvb = _mm(c_kv, layer["wkv_b"], quant).reshape(t, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]

    def rows(qn, qr, pb):
        s = (jnp.einsum("tnd,snd->nts", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("tnr,sr->nts", qr, k_rope, precision=HIGHEST)
             ) / math.sqrt(dn + dr)
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s, -1e30)
        return jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    if q_block >= t or t % q_block:
        ctx = rows(q_nope, q_rope, pos)
    else:
        nb = t // q_block
        ctx = jax.lax.map(
            lambda a: rows(*a),
            (q_nope.reshape(nb, q_block, nh, dn),
             q_rope.reshape(nb, q_block, nh, dr),
             pos.reshape(nb, q_block))).reshape(t, nh, dv)
    return _mm(ctx.reshape(t, nh * dv), layer["wo"], quant)


def router_scores(x, gate_w):
    return jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                  gate_w.astype(jnp.float32),
                                  precision=HIGHEST))


def chosen_experts(s, gate_bias, d):
    """The experts [T, k] chosen from the scores s [T, E]: the top-k of
    score + bias inside the ``topk_group`` groups whose two best sum
    highest."""
    c = s + gate_bias.astype(jnp.float32)[None, :]
    g = d["n_group"]
    if g > 1:
        per = c.reshape(c.shape[0], g, -1)
        group_score = jnp.sum(jnp.sort(per, axis=-1)[..., -2:], axis=-1)
        worst_kept = jnp.sort(group_score, axis=-1)[:, -d["topk_group"]]
        c = jnp.where((group_score >= worst_kept[:, None])[:, :, None],
                      per, -jnp.inf).reshape(c.shape)
    return jax.lax.top_k(c, d["top_k"])[1]


def router_weights(x, gate_w, gate_bias, d):
    """[T, E] dense combine weights over ALL experts and the chosen
    experts [T, k]: sigmoid scores; the choice is the top-k of score +
    bias inside the ``topk_group`` groups whose two best sum highest; the
    chosen scores themselves (WITHOUT the bias) normalised and scaled."""
    s = router_scores(x, gate_w)
    top_i = chosen_experts(s, gate_bias, d)
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * d["scaling"]
    cw = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, gate_w.shape[1], dtype=jnp.float32))
    return cw, top_i


def _swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def ffn(p, x, d, quant=None, shared=True):
    """The feed-forward of one layer over x: [T, H] float32 (normed): one
    dense SwiGLU, or every expert HELD here on every token, one at a
    time, combined through its column of the dense weight matrix, plus
    (``shared``) the shared expert."""
    if p["gate_w"].shape[1] == 1:
        return _swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0],
                       quant)
    cw, _ = router_weights(x, p["gate_w"], p["gate_bias"], d)

    def one(acc, e):
        y = _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], quant)
        return acc + cw[:, d["expert_first"] + e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(p["w_up"].shape[0]))
    if shared and "shared_w_up" in p:
        out = out + _swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                            p["shared_w_down"], quant)
    return out


def _dims_key(d):
    return tuple(sorted(d.items()))


def _mixer(layer, h, d, kind, quant=None, **state):
    """(the mixer's output, the delta-rule state it ends on or None)."""
    if kind == "kda":
        return kda(layer, h, d, quant, **state)
    return mla(layer, h, d, quant), None


@functools.partial(jax.jit, static_argnames=("dkey", "kind", "quant"))
def _block(layer, x, dkey, kind, quant):
    d = dict(dkey)
    x = x + _mixer(layer, _rms(x, layer["attn_norm"], d["eps"]), d, kind,
                   quant)[0]
    return x + ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]), d,
                   quant)


@functools.partial(jax.jit,
                   static_argnames=("dkey", "kind", "state_round", "last"))
def _block_state(layer, x, n_valid, dkey, kind, state_round, last):
    """:func:`_block` that also hands out the state after ``n_valid``
    tokens; ``last``: nothing is wanted past this layer's mixer."""
    d = dict(dkey)
    y, s = _mixer(layer, _rms(x, layer["attn_norm"], d["eps"]), d, kind,
                  n_valid=n_valid, state_round=state_round)
    if last:
        return x, s
    x = x + y
    return x + ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]),
                   d), s


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
        load = jnp.zeros((n_exp,), jnp.float32).at[
            chosen_experts(s, bias, d).reshape(-1)].add(1.0)
        return bias + rate * jnp.sign(mean_load - load), None

    rates = jnp.repeat(jnp.asarray(BALANCE_RATES, jnp.float32),
                       BALANCE_STEPS)
    return jax.lax.scan(step, jnp.zeros((n_exp,), jnp.float32), rates)[0]


@functools.partial(jax.jit, static_argnames=("dkey", "kind"))
def _ffn_input(layer, x, dkey, kind):
    d = dict(dkey)
    x = x + _mixer(layer, _rms(x, layer["attn_norm"], d["eps"]), d, kind)[0]
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


# ----------------------------------------------------------------------
# the recurrent-state comparison: what the served tokens cannot tell
# ----------------------------------------------------------------------

def final_states(params, d, tokens, n_valid, layers, state_round=None):
    """The delta-rule state [N, D, D] of every 'kda' layer among the first
    ``layers`` layers after ``n_valid`` tokens of ONE sequence.  tokens:
    [T] int32, padded past ``n_valid`` (a pad leaves every state alone)."""
    x = params["embed"][tokens].astype(jnp.float32)
    dkey, out = _dims_key(d), []
    for li in range(layers):
        x, s = _block_state(params["layers"][li], x, n_valid, dkey,
                            d["kinds"][li], state_round, li == layers - 1)
        if s is not None:
            out.append(s)
    return out


def state_gaps(params, d, streams, t_pad, layers=1, control=None):
    """For each ``(tokens, states)`` (the tokens a slot of the TIMED engine
    has consumed, and the float32 state ``[n, N, D, D]`` its first ``n``
    'kda' layers then held): the reference's recurrence over the same
    tokens, and per layer ``|S_engine - S_ref| / |S_ref|`` (Frobenius).

    What the first layer's gap reads: its inputs are embedding rows, the
    same numbers on both sides, so the gap is the recurrence's own
    arithmetic (the projections' bfloat16, 0.2 % of the state) and NOT
    what earlier layers' roundings and routing flips added: a state kept
    or decayed in a lower precision than float32 adds a rounding of the
    WHOLE state every token, which sums over the state's memory (tens to
    hundreds of tokens) where a token's own rounding enters once.  With
    ``control`` (a dtype name) the state read is instead the reference's
    own with the state rounded to that dtype after every token.
    Returns ``{"widest": the first layer's largest gap over the streams,
    "per_stream": [[gap per layer]]}``."""
    per = []
    for tokens, states in streams:
        toks = np.zeros((t_pad,), np.int32)
        toks[:len(tokens)] = tokens
        args = (params, d, jnp.asarray(toks), len(tokens), layers)
        ref = [np.asarray(s) for s in final_states(*args)]
        got = (states if control is None else
               [np.asarray(s) for s in final_states(
                   *args, state_round=control)])
        per.append([float(np.linalg.norm(g - r) / np.linalg.norm(r))
                    for g, r in zip(got, ref)])
    return {"widest": max((p[0] for p in per), default=float("nan")),
            "per_stream": per}
