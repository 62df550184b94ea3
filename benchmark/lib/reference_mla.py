"""The plain reference of the latent-attention configurations
(``joyai_flash``): the published block's mathematics in straightforward
``jax.numpy``, float32 at matmul precision "highest", the prefill form
only: no cache, no absorption, every expert evaluated on every token (one
expert at a time, so that 7168 tokens x 256 experts fit beside the served
weights).  It imports nothing of the program; the sibling ``reference.py``
lends its float8 rounding, its matmul, its norm and its seed key.

x is the normed input of a block, h indexes the heads, eps 1e-6:

    c_q = RMSNorm(x W_qa);  [q_nope_h | q_rope_h] = c_q W_qb;  RoPE(q_rope_h)
    [c | k_r] = x W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r), one key for
    all heads;  [k_nope_h | v_h] = c_kv W_kvb
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_rope(s))
                    / sqrt(d_nope + d_rope),  causal softmax,  o_h = sum p v_h,
    output concat_h(o_h) W_o.   RoPE rotates ADJACENT pairs (2i, 2i+1).
    router: s = sigmoid(x W_g) in float32; the experts are the top-k of
    s + b; their weights are s_i / (sum of the chosen + 1e-20) * scaling;
    y = sum_i w_i E_i(x) + E_shared(x), every expert SwiGLU.
    The first ``first_k_dense_replace`` layers carry a dense SwiGLU of the
    published ``intermediate_size``.  Pre-norm residual blocks, final
    RMSNorm, untied head.

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
HIGHEST, _mm, _rms, seed_key = (_ref.HIGHEST, _ref._mm, _ref._rms,
                                _ref.seed_key)

#: standard deviation of the selection bias ``b`` drawn from the seed (the
#: sigmoid scores lie in (0, 1) with a spread of about 0.2: a bias of this
#: size changes the chosen set of most tokens and rules none)
BIAS_STD = 0.05


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in
    the source's own (Hugging Face) key names: at the top level of the
    file, or grouped under ``model`` as the sibling's configurations."""
    m = config.get("model", config)
    if m.get("scoring_func") != "sigmoid" or m.get("n_group", 1) != 1:
        raise KeyError("reference_mla describes a sigmoid router with one "
                       "group; this configuration states another")
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "heads": m["num_attention_heads"],
        "q_rank": m["q_lora_rank"],
        "kv_rank": m["kv_lora_rank"],
        "d_nope": m["qk_nope_head_dim"],
        "d_rope": m["qk_rope_head_dim"],
        "d_v": m["v_head_dim"],
        "vocab": m["vocab_size"],
        "experts": m["n_routed_experts"],
        "top_k": m["num_experts_per_tok"],
        "shared": m["n_shared_experts"],
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
    """The whole model's weights in the tree layout the program's entry
    points take for this architecture (``embed``, ``final_norm``,
    ``lm_head``, ``layers`` of ``attn_norm / ffn_norm / wq_a / q_a_norm /
    wq_b / wkv_a / kv_a_norm / wkv_b / wo / moe``; a mixture layer's
    ``moe`` holds ``gate_w``, the selection bias ``gate_bias`` (float32,
    drawn NON-ZERO from the seed), the stacked experts and the shared
    expert; a dense layer's holds one expert of the dense width), one
    jitted call a layer.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, v = d["hidden"], d["heads"], d["vocab"]
    rq, rkv = d["q_rank"], d["kv_rank"]
    dn, dr, dv = d["d_nope"], d["d_rope"], d["d_v"]

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    def ffn(key, n_exp, inter, n_shared):
        ks = jax.random.split(key, 8)
        p = {"gate_w": nrm(ks[0], (h, n_exp), h),
             "w_up": nrm(ks[1], (n_exp, h, inter), h),
             "b_up": jnp.zeros((n_exp, inter), dt),
             "w_down": nrm(ks[2], (n_exp, inter, h), inter),
             "b_down": jnp.zeros((n_exp, h), dt),
             "w_gate": nrm(ks[3], (n_exp, h, inter), h)}
        if n_exp > 1:
            p["gate_bias"] = BIAS_STD * jax.random.normal(
                ks[7], (n_exp,), jnp.float32)
        if n_shared:
            si = inter * n_shared
            p["shared_w_up"] = nrm(ks[4], (h, si), h)
            p["shared_w_down"] = nrm(ks[5], (si, h), si)
            p["shared_w_gate"] = nrm(ks[6], (h, si), h)
        return p

    @functools.partial(jax.jit, static_argnames=("dense",))
    def layer(key, dense):
        lk = jax.random.split(key, 6)
        return {
            "attn_norm": jnp.ones((h,), dt), "ffn_norm": jnp.ones((h,), dt),
            "wq_a": nrm(lk[0], (h, rq), h),
            "q_a_norm": jnp.ones((rq,), dt),
            "wq_b": nrm(lk[1], (rq, nh * (dn + dr)), rq),
            "wkv_a": nrm(lk[2], (h, rkv + dr), h),
            "kv_a_norm": jnp.ones((rkv,), dt),
            "wkv_b": nrm(lk[3], (rkv, nh * (dn + dv)), rkv),
            "wo": nrm(lk[4], (nh * dv, h), nh * dv),
            "moe": (ffn(lk[5], 1, d["dense_inter"], 0) if dense
                    else ffn(lk[5], d["experts"], d["inter"], d["shared"])),
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
                              dense=li < d["first_dense"])
                        for li in range(d["layers"])]
    return params


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def rope_adjacent(x, positions, theta):
    """x: [T, D] or [T, N, D]; rotates the pairs (2i, 2i+1) of D."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq          # [T, half]
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def attention(layer, x, d, quant=None, q_block=512):
    """Causal multi-head latent attention over one sequence x: [T, H]
    float32 (already normed), the scores in blocks of ``q_block`` rows."""
    t = x.shape[0]
    nh, dn, dr, dv = d["heads"], d["d_nope"], d["d_rope"], d["d_v"]
    rkv = d["kv_rank"]
    pos = jnp.arange(t)
    c_q = _rms(_mm(x, layer["wq_a"], quant), layer["q_a_norm"], d["eps"])
    q = _mm(c_q, layer["wq_b"], quant).reshape(t, nh, dn + dr)
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


def router_weights(x, gate_w, gate_bias, d):
    """[T, E] dense combine weights and the chosen experts [T, k]:
    sigmoid scores, top-k of score + bias, the chosen scores themselves
    (WITHOUT the bias) normalised and scaled."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               gate_w.astype(jnp.float32),
                               precision=HIGHEST))
    _, top_i = jax.lax.top_k(s + gate_bias.astype(jnp.float32)[None, :],
                             d["top_k"])
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


def ffn(p, x, d, quant=None):
    """The feed-forward of one layer over x: [T, H] float32 (normed): one
    dense SwiGLU, or every routed expert on every token, one expert at a
    time, combined through the dense weight matrix, plus the shared
    expert."""
    n_exp = p["w_up"].shape[0]
    if n_exp == 1:
        return _swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0],
                       quant)
    cw, _ = router_weights(x, p["gate_w"], p["gate_bias"], d)

    def one(acc, e):
        y = _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], quant)
        return acc + cw[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_exp))
    if "shared_w_up" in p:
        out = out + _swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                            p["shared_w_down"], quant)
    return out


def _dims_key(d):
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnames=("dkey", "quant"))
def _block(layer, x, dkey, quant):
    d = dict(dkey)
    x = x + attention(layer, _rms(x, layer["attn_norm"], d["eps"]), d, quant)
    return x + ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]), d,
                   quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head, x, rows, eps, quant):
    return _mm(_rms(x[rows], final_norm, eps), lm_head, quant)


def forward_logits(params, d, tokens, rows, quant=None):
    """Reference logits of ONE sequence.  tokens: [T] int32 (padded past
    the true end: causality keeps pads out of earlier rows); rows: [R]
    int32 positions whose logits are wanted.  Layer by layer, so only one
    layer's float32 copies live at a time.  Returns [R, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    dkey = _dims_key(d)
    for layer in params["layers"]:
        x = _block(layer, x, dkey, quant)
    return _head(params["final_norm"], params["lm_head"], x, rows,
                 d["eps"], quant)


# ----------------------------------------------------------------------
# the served-model comparison (the sibling's, over this forward pass)
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
