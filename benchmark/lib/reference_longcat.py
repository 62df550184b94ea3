"""The plain reference of the shortcut-connected latent-attention
configuration (``longcat_flash_omni``): the published layer's mathematics
in straightforward ``jax.numpy``, float32 at matmul precision "highest",
the prefill form only: no cache, no absorption, no sort; every token's
choices through a dense [T, router width] weight matrix, the experts held
here one at a time.  It imports nothing of the program; the sibling
``reference.py`` lends its float8 rounding, its matmul, its norm and its
seed key, ``reference_mla.py`` its adjacent-pair rotation and its SwiGLU.

One PUBLISHED layer (d the width, eps 1e-5) is two attention sublayers
and two dense FFNs around ONE mixture whose output joins the residual
stream a sublayer later (the order is the published
``modeling_longcat_flash.py``'s shortcut-connected mixture):

    h1 = h0 + MLA_a(RMSNorm(h0));   u = RMSNorm(h1);   s = Mixture(u)
    h2 = h1 + FFN_a(u)
    h3 = h2 + MLA_b(RMSNorm(h2))
    h4 = h3 + FFN_b(RMSNorm(h3)) + s

    MLA (x normed, h the heads): c_q = a_q RMSNorm(x W_qa),
    [q_nope_h | q_rope_h] = c_q W_qb, RoPE(q_rope_h);  [c | k_r] = x W_kva,
    c_kv = a_kv RMSNorm(c), k_rope = RoPE(k_r), one key for all heads;
    [k_nope_h | v_h] = c_kv W_kvb;  a_q = sqrt(d / q_lora_rank), a_kv =
    sqrt(d / kv_lora_rank) (``mla_scale_q_lora`` / ``mla_scale_kv_lora``:
    a_q on both parts of every head's query, a_kv on k_nope and v and NOT
    on the shared rotary key);  score_h(t, s) = (q_nope_h(t).k_nope_h(s) +
    q_rope_h(t).k_rope(s)) / sqrt(d_nope + d_rope), causal softmax,
    output concat_h(sum p v_h) W_o.  RoPE rotates ADJACENT pairs.
    FFN: W_down(silu(W_gate x) * W_up x), no bias.
    Mixture: p = softmax(u W_r) in float32 over n_routed_experts +
    zero_expert_num outputs; the moe_topk chosen are the top-k of p + b
    (the selection bias); their weights are the chosen p themselves, NOT
    normalised, times routed_scaling_factor; output e < n_routed_experts
    is a SwiGLU expert of width expert_ffn_hidden_size, e >= that is the
    IDENTITY (u itself);  s = sum_j w_j E_{e_j}(u).  No shared expert.

This chip holds FFN experts ``expert_first`` .. + ``experts`` - 1 of the
published count (what the others would have added is left out) and
computes every identity expert for its own tokens.  A final RMSNorm, an
untied head over the vocabulary's slice.

``quant`` names the CONTROLS, joined by "+": ``fp8`` rounds both operands
of every linear layer to float8 e4m3 (the sibling's ``_mm``); ``no_zero``
leaves the identity experts' term out; ``early_join`` adds the mixture's
output where it is read (h2) and not a sublayer later; ``no_scale_q`` /
``no_scale_kv`` leave a rank scale out.  The last four are the mechanism's
own controls: a program that made that mistake.
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
# the other latent-attention reference lends its rotation and its SwiGLU
rope_adjacent, _swiglu = (_sibling("reference_mla").rope_adjacent,
                          _sibling("reference_mla")._swiglu)

#: the selection bias is FITTED, as a checkpoint's is: after a batch, raise
#: the bias of an output that got fewer rows than the mean by a fixed step,
#: lower it otherwise, from zero, on PROBE_TOKENS tokens drawn from the
#: seed, BALANCE_STEPS steps at each of BALANCE_RATES.  The rates are a
#: softmax's: a probability over 768 outputs is 0.0013 in the mean and
#: 0.005-0.02 where the top-12 is decided.
PROBE_TOKENS, BALANCE_STEPS, BALANCE_RATES = 2048, 120, (3e-4, 1e-4, 3e-5)


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names, at the top level of the file or under
    ``model``; the published expert count under ``published``, the share
    held under ``held``."""
    m = config.get("model", config)
    if (m.get("attention_method") != "MLA"
            or m.get("zero_expert_type") != "identity"):
        raise KeyError("reference_longcat describes latent attention and "
                       "identity zero-compute experts; this configuration "
                       "states another")
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_layers"],            # PUBLISHED (double) layers
        "heads": m["num_attention_heads"],
        "q_rank": m["q_lora_rank"],
        "kv_rank": m["kv_lora_rank"],
        "d_nope": m["qk_nope_head_dim"],
        "d_rope": m["qk_rope_head_dim"],
        "d_v": m["v_head_dim"],
        "scale_q": bool(m["mla_scale_q_lora"]),
        "scale_kv": bool(m["mla_scale_kv_lora"]),
        "vocab": m["vocab_size"],
        "router_experts": config.get("published", m)["n_routed_experts"],
        "experts": m["n_routed_experts"],
        "expert_first": config.get("held", {}).get("expert_first", 0),
        "zero": m["zero_expert_num"],
        "top_k": m["moe_topk"],
        "inter": m["expert_ffn_hidden_size"],
        "dense_inter": m["ffn_hidden_size"],
        "scaling": float(m["routed_scaling_factor"]),
        "rope_theta": float(m["rope_theta"]),
        "eps": float(m["rms_norm_eps"]),
        "param_dtype": config.get("served", {}).get(
            "param_dtype", m.get("torch_dtype", "bfloat16")),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict, balance: bool = True):
    """The model's weights in the tree layout the program's entry points
    take for this architecture: ``embed``, ``final_norm``, ``lm_head`` and
    ``layers``, TWO entries a published layer (the program counts
    sublayers), each ``attn_norm / ffn_norm / wq_a / q_a_norm / wq_b /
    wkv_a / kv_a_norm / wkv_b / wo / moe`` with ``moe`` one dense expert
    of the dense width; the first of a pair also holds ``branch``, the
    mixture: ``gate_w`` over every output of the router, the selection
    bias ``gate_bias`` (float32, fitted: :func:`balance_biases`) and the
    stacked FFN experts held here.  One jitted call a sublayer; ``seed``
    may exceed 32 bits.

    Every matrix is normal / sqrt(fan_in) EXCEPT the two up-projections
    behind a scaled latent, ``wq_b`` and ``wkv_b``, which are normal /
    sqrt(hidden): the rank scales are the published design's variance
    alignment, a_q^2 x rank_q / hidden = a_kv^2 x rank_kv / hidden = 1,
    so queries, keys and values come out at unit variance as in every
    other latent-attention model here.  Drawn / sqrt(rank) the scales
    would spread the scores 5.8 wide where those models have 1: every
    head picks single keys, and bfloat16 ties flip heads' picks (the
    first chip reading: ``served_gap_mean`` 0.111-0.115, the served token
    the reference's best on 18 % of the positions)."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, v = d["hidden"], d["heads"], d["vocab"]
    rq, rkv = d["q_rank"], d["kv_rank"]
    dn, dr, dv = d["d_nope"], d["d_rope"], d["d_v"]
    width = d["router_experts"] + d["zero"]

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    def ffn(key, n_exp, n_out, inter):
        ks = jax.random.split(key, 4)
        p = {"gate_w": nrm(ks[0], (h, n_out), h),
             "w_up": nrm(ks[1], (n_exp, h, inter), h),
             "b_up": jnp.zeros((n_exp, inter), dt),
             "w_down": nrm(ks[2], (n_exp, inter, h), inter),
             "b_down": jnp.zeros((n_exp, h), dt),
             "w_gate": nrm(ks[3], (n_exp, h, inter), h)}
        if n_out > 1:
            p["gate_bias"] = jnp.zeros((n_out,), jnp.float32)
        return p

    @functools.partial(jax.jit, static_argnames=("reads",))
    def sublayer(key, reads):
        lk = jax.random.split(key, 7)
        layer = {
            "attn_norm": jnp.ones((h,), dt), "ffn_norm": jnp.ones((h,), dt),
            "wq_a": nrm(lk[0], (h, rq), h),
            "q_a_norm": jnp.ones((rq,), dt),
            "wq_b": nrm(lk[1], (rq, nh * (dn + dr)),
                        h if d["scale_q"] else rq),
            "wkv_a": nrm(lk[2], (h, rkv + dr), h),
            "kv_a_norm": jnp.ones((rkv,), dt),
            "wkv_b": nrm(lk[3], (rkv, nh * (dn + dv)),
                         h if d["scale_kv"] else rkv),
            "wo": nrm(lk[4], (nh * dv, h), nh * dv),
            "moe": ffn(lk[5], 1, 1, d["dense_inter"]),
        }
        if reads:
            layer["branch"] = ffn(lk[6], d["experts"], width, d["inter"])
        return layer

    @jax.jit
    def ends(key):
        k0, k1 = jax.random.split(key)
        return {"embed": (jax.random.normal(k0, (v, h), jnp.float32)
                          * 0.02).astype(dt),
                "final_norm": jnp.ones((h,), dt),
                "lm_head": nrm(k1, (h, v), h)}

    params = ends(seed_key(seed, 0))
    params["layers"] = [sublayer(seed_key(seed, 1 + li), reads=li % 2 == 0)
                        for li in range(2 * d["layers"])]
    return balance_biases(params, d, seed) if balance else params


# ----------------------------------------------------------------------
# the layer, plainly
# ----------------------------------------------------------------------

def _flags(quant):
    """(what ``_mm`` rounds to, the mechanism's controls) of a ``quant``
    name."""
    flags = set(quant.split("+")) if quant else set()
    return ("fp8" if "fp8" in flags else None), flags


def attention(layer, x, d, quant=None, q_block=256):
    """Causal multi-head latent attention with the two rank scales over
    one sequence x: [T, H] float32 (already normed), the scores in blocks
    of ``q_block`` rows."""
    q8, flags = _flags(quant)
    t = x.shape[0]
    nh, dn, dr, dv = d["heads"], d["d_nope"], d["d_rope"], d["d_v"]
    rkv = d["kv_rank"]
    a_q = (math.sqrt(d["hidden"] / d["q_rank"])
           if d["scale_q"] and "no_scale_q" not in flags else 1.0)
    a_kv = (math.sqrt(d["hidden"] / rkv)
            if d["scale_kv"] and "no_scale_kv" not in flags else 1.0)
    pos = jnp.arange(t)
    c_q = a_q * _rms(_mm(x, layer["wq_a"], q8), layer["q_a_norm"], d["eps"])
    q = _mm(c_q, layer["wq_b"], q8).reshape(t, nh, dn + dr)
    q_nope = q[..., :dn]
    q_rope = rope_adjacent(q[..., dn:], pos, d["rope_theta"])
    kv = _mm(x, layer["wkv_a"], q8)                          # [T, rkv + dr]
    c_kv = a_kv * _rms(kv[:, :rkv], layer["kv_a_norm"], d["eps"])
    k_rope = rope_adjacent(kv[:, rkv:], pos, d["rope_theta"])   # [T, dr]
    kvb = _mm(c_kv, layer["wkv_b"], q8).reshape(t, nh, dn + dv)
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
    return _mm(ctx.reshape(t, nh * dv), layer["wo"], q8)


def router_probs(u, gate_w):
    """softmax(u W_r) in float32 over every output: [T, width]."""
    return jax.nn.softmax(jnp.dot(
        u.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=HIGHEST), axis=-1)


def chosen(p, bias, d):
    """The top-k outputs of ``p + bias``: [T, k]."""
    return jax.lax.top_k(p + bias.astype(jnp.float32)[None, :],
                         d["top_k"])[1]


def router_weights(u, gate_w, gate_bias, d):
    """[T, width] dense combine weights and the chosen outputs [T, k]:
    softmax probabilities, top-k of probability + bias, the chosen
    probabilities themselves (WITHOUT the bias, NOT normalised) times the
    scaling factor."""
    p = router_probs(u, gate_w)
    top_i = chosen(p, gate_bias, d)
    w = jnp.take_along_axis(p, top_i, axis=-1) * d["scaling"]
    cw = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, gate_w.shape[1], dtype=jnp.float32))
    return cw, top_i


def dense_ffn(p, x, quant=None):
    return _swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0],
                   _flags(quant)[0])


def mixture(p, u, d, quant=None):
    """s = sum_j w_j E_{e_j}(u) over u: [T, H] float32 (normed): the FFN
    experts held here (``expert_first`` ..), one at a time, each against
    its column of the dense weight matrix; the identity experts as the
    sum of their columns times u; the FFN experts held elsewhere left
    out."""
    q8, flags = _flags(quant)
    cw, _ = router_weights(u, p["gate_w"], p["gate_bias"], d)
    first, held = d["expert_first"], p["w_up"].shape[0]

    def one(acc, e):
        y = _swiglu(u, p["w_gate"][e], p["w_up"][e], p["w_down"][e], q8)
        return acc + cw[:, first + e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    if "no_zero" not in flags:
        w_zero = jnp.sum(cw[:, d["router_experts"]:], axis=-1)
        out = out + w_zero[:, None] * u
    return out


def _dims_key(d):
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnames=("dkey", "quant"))
def _layer(a, b, h0, dkey, quant):
    """One published layer: sublayers ``a`` (which reads the mixture) and
    ``b`` (after whose dense FFN it joins)."""
    d = dict(dkey)
    early = "early_join" in _flags(quant)[1]
    h1 = h0 + attention(a, _rms(h0, a["attn_norm"], d["eps"]), d, quant)
    u = _rms(h1, a["ffn_norm"], d["eps"])
    s = mixture(a["branch"], u, d, quant)
    h2 = h1 + dense_ffn(a["moe"], u, quant) + (s if early else 0.0)
    h3 = h2 + attention(b, _rms(h2, b["attn_norm"], d["eps"]), d, quant)
    return (h3 + dense_ffn(b["moe"], _rms(h3, b["ffn_norm"], d["eps"]),
                           quant) + (0.0 if early else s))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head, x, rows, eps, quant):
    return _mm(_rms(x[rows], final_norm, eps), lm_head, _flags(quant)[0])


def forward_logits(params, d, tokens, rows, quant=None):
    """Reference logits of ONE sequence.  tokens: [T] int32 (padded past
    the true end: causality keeps pads out of earlier rows); rows: [R]
    int32 positions whose logits are wanted.  Published layer by published
    layer, so only one layer's float32 copies live at a time.  Returns
    [R, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    dkey = _dims_key(d)
    layers = params["layers"]
    for a, b in zip(layers[0::2], layers[1::2]):
        x = _layer(a, b, x, dkey, quant)
    return _head(params["final_norm"], params["lm_head"], x, rows,
                 d["eps"], quant)


# ----------------------------------------------------------------------
# the selection bias, fitted
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey",))
def _fitted_bias(u, gate_w, dkey):
    """The bias that balances the outputs' loads (FFN and identity alike)
    over the rows u [T, H] (normed): the checkpoint's update rule from
    zero, each step on the whole probe."""
    d = dict(dkey)
    p = router_probs(u, gate_w)
    n_out = gate_w.shape[1]
    mean_load = u.shape[0] * d["top_k"] / n_out

    def step(bias, rate):
        load = jnp.zeros((n_out,), jnp.float32).at[
            chosen(p, bias, d).reshape(-1)].add(1.0)
        return bias + rate * jnp.sign(mean_load - load), None

    rates = jnp.repeat(jnp.asarray(BALANCE_RATES, jnp.float32),
                       BALANCE_STEPS)
    return jax.lax.scan(step, jnp.zeros((n_out,), jnp.float32), rates)[0]


@functools.partial(jax.jit, static_argnames=("dkey",))
def _mixture_input(a, h0, dkey):
    d = dict(dkey)
    h1 = h0 + attention(a, _rms(h0, a["attn_norm"], d["eps"]), d)
    return _rms(h1, a["ffn_norm"], d["eps"])


def balance_biases(params, d, seed):
    """Fit every mixture's selection bias, first layer first: a probe
    sequence from the seed goes through the layers (this file's own
    forward pass), each router is balanced on the rows that reach it, and
    the probe goes on through the layer as balanced."""
    dkey = _dims_key(d)
    probe = jax.random.randint(seed_key(seed, 10_000), (PROBE_TOKENS,), 1,
                               d["vocab"])
    x = params["embed"][probe].astype(jnp.float32)
    layers = params["layers"]
    for a, b in zip(layers[0::2], layers[1::2]):
        a["branch"]["gate_bias"] = _fitted_bias(
            _mixture_input(a, x, dkey), a["branch"]["gate_w"], dkey)
        x = _layer(a, b, x, dkey, None)
    return params


def zero_choice_share(params, d, seed, tokens=PROBE_TOKENS):
    """The share of the chosen outputs that are identity experts, a layer,
    on a probe of its own from the seed (a third with no bias and with a
    balanced one: 256 of 768 outputs)."""
    dkey = _dims_key(d)
    probe = jax.random.randint(seed_key(seed, 10_001), (tokens,), 1,
                               d["vocab"])
    x = params["embed"][probe].astype(jnp.float32)
    layers, shares = params["layers"], []
    for a, b in zip(layers[0::2], layers[1::2]):
        u = _mixture_input(a, x, dkey)
        top_i = chosen(router_probs(u, a["branch"]["gate_w"]),
                       a["branch"]["gate_bias"], d)
        shares.append(float(jnp.mean(top_i >= d["router_experts"])))
        x = _layer(a, b, x, dkey, None)
    return shares


# ----------------------------------------------------------------------
# the served-model comparison (the siblings', over this forward pass)
# ----------------------------------------------------------------------

def served_token_gaps(params, d, streams, t_pad, r_pad, control=None):
    """For each served stream ``(prompt, served_tokens)``: run the
    reference once over prompt + served tokens and read, at every served
    position, how far the served token's logit lies below the reference's
    best, as a share of the largest logit magnitude among the compared
    rows.  With ``control`` (a ``quant`` name) the token read at each
    position is instead the one that control puts first.  Returns
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
