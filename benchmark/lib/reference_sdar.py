"""The plain reference of the block-diffusion mixture models
(``sdar_30b_a3b``: SDAR-30B-A3B-Chat, ``model_type`` sdar_moe): the
published layer's mathematics and the family's generation by diffusion over
blocks in straightforward ``jax.numpy``, float32 at matmul precision
"highest": attention over the whole sequence under an explicit mask, no
kernel, no cache, no batching, every expert evaluated on every token (one
at a time, so that 3 584 rows fit beside the served weights).  It imports
nothing of the program; the sibling ``reference.py`` lends the float8
rounding, the matmul, the norm, the half-split RoPE and the seed key.

Layer (all alike): h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h)).
Attn: q as N heads of D, k and v as N_kv heads of D (N / N_kv query heads a
    K/V head), no bias; RMSNorm over each head's D columns of q and of k
    (``q_norm`` / ``k_norm``) BEFORE RoPE; RoPE by halves, plain positions;
    softmax(q k^T / sqrt(D)) under the mask M; W_o.
MoE: p = softmax(x W_g) over all experts, the ``num_experts_per_tok``
    largest kept and renormalised to sum 1 (``norm_topk_prob``); SwiGLU
    experts; no shared expert, no bias, no scale.
Head: logits = RMSNorm(x) W_head, NOT tied to the embedding.  Row i's
    logits are position i's OWN token (no shift).

M is BLOCK-causal, L = ``block_length``: position i sees position j iff
floor(j / L) <= floor(i / L).  Generation, block by block: the first
floor(P / L) L prompt tokens are clean context, the prompt's tail opens the
first block revealed, every other row of a block starts as the ``[MASK]``
token.  A denoising step forwards the block's L rows as they stand over the
CLEAN earlier blocks, takes every masked row's greedy token (the ``[MASK]``
id's logit left out) and its confidence softmax(logits)[token], and reveals
the L / S rows of highest confidence (ties to the lower index); after S
steps the clean block is what later blocks see (the commit).

What a step saw is scored by the family's TRAINING-TIME form, one forward a
request: the answer's blocks in their state before step s, for every s,
appended to the clean sequence at their own positions; a noisy block sees
itself and the clean blocks before it, the clean part is block-causal
(:func:`block_rows`, :func:`_visible`).

``quant="fp8"`` is the precision CONTROL (both operands of every linear
layer rounded to float8 e4m3, the sibling's ``_mm``); ``mask=`` names the
two mistakes of the mechanism the cell must tell: ``"causal_block"`` (plain
causal attention inside a block, clean and noisy alike) and ``"no_commit"``
(later blocks read the K/V an earlier block had at its LAST denoising step,
rows still masked then and all, not the clean block's).
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

#: the controls a cell may name: a ``quant`` of the linear layers, or a
#: ``mask`` of the attention
CONTROLS = {"fp8": {"quant": "fp8"},
            "causal_block": {"mask": "causal_block"},
            "no_commit": {"mask": "no_commit"}}


def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json`` in the
    source's own key names at the top level of the file; what the source
    does not state (the block length, the ``[MASK]`` id) under
    ``generation``, each with its reason under ``assumed``."""
    m = config.get("model", config)
    if (m.get("attention_bias") or m.get("mlp_only_layers")
            or m.get("decoder_sparse_step", 1) != 1
            or m.get("use_sliding_window") or m.get("tie_word_embeddings")):
        raise KeyError("reference_sdar describes layers all alike (a "
                       "mixture each, no bias, no window) and an untied "
                       "head; this configuration states another")
    gen = config["generation"]
    block = int(gen["block_length"])
    if block < 2 or block & (block - 1):
        raise KeyError(f"block_length {block} is no power of two >= 2")
    return {
        "hidden": m["hidden_size"],
        "layers": m["num_hidden_layers"],
        "heads": m["num_attention_heads"],
        "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"],
        "vocab": m["vocab_size"],
        "experts": m["num_experts"],
        "top_k": m["num_experts_per_tok"],
        "inter": m["moe_intermediate_size"],
        "norm_topk": bool(m["norm_topk_prob"]),
        "rope_theta": float(m["rope_theta"]),
        "eps": float(m["rms_norm_eps"]),
        "block": block,
        "mask_id": int(gen["mask_token_id"]),
        "param_dtype": config.get("served", {}).get(
            "param_dtype", m.get("torch_dtype", "bfloat16")),
    }


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the program's tree layout
# ----------------------------------------------------------------------

def make_params(seed: int, d: dict):
    """The model's weights in the tree layout the program's entry points
    take (``embed``, ``final_norm``, ``lm_head``, ``layers`` of
    ``attn_norm / ffn_norm / wq / wk / wv / wo / q_norm / k_norm / moe``),
    a jitted call a layer.  The drawing rule: every matrix normal /
    sqrt(fan_in) from ``seed_key(seed, stream)`` (stream 0 the embedding x
    0.02 and the head, an array of its own; stream 1 + l layer l), every
    norm one, no bias.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, nkv, dh, v = (d["hidden"], d["heads"], d["kv_heads"],
                         d["head_dim"], d["vocab"])
    n_exp, inter = d["experts"], d["inter"]

    def nrm(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan)).astype(dt)

    @jax.jit
    def layer(key):
        ks = jax.random.split(key, 8)
        return {
            "attn_norm": jnp.ones((h,), dt), "ffn_norm": jnp.ones((h,), dt),
            "wq": nrm(ks[0], (h, nh * dh), h),
            "wk": nrm(ks[1], (h, nkv * dh), h),
            "wv": nrm(ks[2], (h, nkv * dh), h),
            "wo": nrm(ks[3], (nh * dh, h), nh * dh),
            "q_norm": jnp.ones((dh,), dt), "k_norm": jnp.ones((dh,), dt),
            "moe": {"gate_w": nrm(ks[4], (h, n_exp), h),
                    "w_up": nrm(ks[5], (n_exp, h, inter), h),
                    "b_up": jnp.zeros((n_exp, inter), dt),
                    "w_down": nrm(ks[6], (n_exp, inter, h), inter),
                    "b_down": jnp.zeros((n_exp, h), dt),
                    "w_gate": nrm(ks[7], (n_exp, h, inter), h)}}

    @jax.jit
    def ends(key):
        k0, k1 = jax.random.split(key)
        return {"embed": (jax.random.normal(k0, (v, h), jnp.float32) * 0.02
                          ).astype(dt),
                "final_norm": jnp.ones((h,), dt),
                "lm_head": nrm(k1, (h, v), h)}

    params = ends(seed_key(seed, 0))
    params["layers"] = [layer(seed_key(seed, 1 + li))
                        for li in range(d["layers"])]
    return params


# ----------------------------------------------------------------------
# the layer, plainly, over rows that say what they are
# ----------------------------------------------------------------------
#
# A row of the sequence is (token, pos, kind): its position and what it is
# a row OF: 0 the clean sequence, 1 + s the copy of the answer's blocks in
# their state before denoising step s, -1 padding (sees itself, seen by
# nothing).  ``t_pre`` is where the answer's blocks start (the prompt's
# whole blocks), ``last`` the kind of the copy before the LAST step.

def _visible(pos_q, kind_q, pos_k, kind_k, block, t_pre, last, mask):
    """[Tq, Tk] bool: which key rows a query row sees."""
    bq, bk = pos_q[:, None] // block, pos_k[None, :] // block
    kq, kk = kind_q[:, None], kind_k[None, :]
    clean_k, same = kk == 0, kq == kk
    if mask == "causal_block":
        own = same & (bq == bk) & (pos_k[None, :] <= pos_q[:, None])
        clean = same & (pos_k[None, :] <= pos_q[:, None])
    else:
        own = same & (bq == bk)
        clean = same & (bk <= bq)
    before = clean_k & (bk < bq)
    if mask == "no_commit":
        # an earlier ANSWER block as its last denoising step left it
        before = jnp.where(pos_k[None, :] < t_pre, before,
                           (kk == last) & (bk < bq))
    seen = jnp.where(kq == 0, clean, before | own)
    self_row = ((pos_q[:, None] == pos_k[None, :]) & same)
    return (seen & (kq >= 0) & (kk >= 0)) | (self_row & (kq < 0))


def attention(layer, x, pos, kind, d, t_pre, last, quant=None, mask="block",
              q_block=512):
    """Grouped-query attention with a norm on every head of q and of k
    before RoPE, over rows x: [T, H] float32 (already normed) under
    :func:`_visible`, the scores in blocks of ``q_block`` rows."""
    t = x.shape[0]
    nh, nkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    q = _mm(x, layer["wq"], quant).reshape(t, nh, dh)
    k = _mm(x, layer["wk"], quant).reshape(t, nkv, dh)
    v = _mm(x, layer["wv"], quant).reshape(t, nkv, dh)
    q = _rope(_rms(q, layer["q_norm"], d["eps"]), pos, d["rope_theta"])
    k = _rope(_rms(k, layer["k_norm"], d["eps"]), pos, d["rope_theta"])
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))

    def rows(qb, pb, kb):
        s = jnp.einsum("tnd,snd->nts", qb, k, precision=HIGHEST) \
            / math.sqrt(dh)
        see = _visible(pb, kb, pos, kind, d["block"], t_pre, last, mask)
        s = jnp.where(see[None], s, -1e30)
        return jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    if q_block >= t or t % q_block:
        ctx = rows(q, pos, kind)
    else:
        nb = t // q_block
        ctx = jax.lax.map(
            lambda a: rows(*a),
            (q.reshape(nb, q_block, nh, dh), pos.reshape(nb, q_block),
             kind.reshape(nb, q_block))).reshape(t, nh, dh)
    return _mm(ctx.reshape(t, nh * dh), layer["wo"], quant)


def router_weights(x, gate_w, d):
    """[T, E] dense combine weights: softmax over all experts, the top-k
    kept and renormalised to sum 1."""
    p = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                               gate_w.astype(jnp.float32),
                               precision=HIGHEST), axis=-1)
    w, top_i = jax.lax.top_k(p, d["top_k"])
    if d["norm_topk"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, gate_w.shape[1], dtype=jnp.float32))


def _swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def ffn(p, x, d, quant=None):
    """The mixture over x: [T, H] float32 (normed): every expert on every
    token, one at a time, combined through its column of the dense weight
    matrix."""
    cw = router_weights(x, p["gate_w"], d)

    def one(acc, e):
        y = _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], quant)
        return acc + cw[:, e][:, None] * y, None

    return jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(p["w_up"].shape[0]))[0]


def _dims_key(d):
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnames=("dkey", "quant", "mask"))
def _block(layer, x, pos, kind, t_pre, last, dkey, quant, mask):
    d = dict(dkey)
    x = x + attention(layer, _rms(x, layer["attn_norm"], d["eps"]), pos,
                      kind, d, t_pre, last, quant, mask)
    return x + ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]), d,
                   quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "mask_id",
                                             "rows_block", "full"))
def _head(final_norm, lm_head, x, rows, picked, eps, quant, mask_id,
          rows_block, full):
    """What the check reads of each of ``rows``' logits (the ``[MASK]``
    id's left out): the best, its token, the log-sum-exp, the largest
    magnitude, and the logits of the tokens ``picked`` [R, K]; the head
    in blocks of ``rows_block`` rows.  ``full``: the logits themselves."""
    h = _rms(x[rows], final_norm, eps)
    if full:
        return _mm(h, lm_head, quant)

    def some(a):
        hb, pb = a
        lg = _mm(hb, lm_head, quant)
        lg = jnp.where(jnp.arange(lg.shape[-1]) == mask_id, -1e30, lg)
        return (jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32),
                jax.nn.logsumexp(lg, -1),
                jnp.max(jnp.where(lg > -1e29, jnp.abs(lg), 0.0), -1),
                jnp.take_along_axis(lg, pb, axis=-1))

    r = h.shape[0]
    nb = max(1, r // rows_block) if r % rows_block == 0 else 1
    out = jax.lax.map(some, (h.reshape(nb, r // nb, -1),
                             picked.reshape(nb, r // nb, -1)))
    return tuple(a.reshape(r, *a.shape[2:]) for a in out)


def forward_rows(params, d, tokens, pos, kind, rows, picked=None, *,
                 t_pre=0, last=0, quant=None, mask="block", full=False):
    """The reference over ONE sequence of rows (token, pos, kind: see
    above).  rows: [R] the rows whose logits are wanted; picked: [R, K]
    tokens whose logits are read there.  Layer by layer, so only one
    layer's float32 copies live at a time.  Returns (best, argmax,
    log-sum-exp, largest magnitude, the picked tokens' logits), each [R]
    or [R, K]; with ``full`` the logits [R, V]."""
    x = params["embed"][tokens].astype(jnp.float32)
    dkey = _dims_key(d)
    pos, kind = jnp.asarray(pos, jnp.int32), jnp.asarray(kind, jnp.int32)
    for layer in params["layers"]:
        x = _block(layer, x, pos, kind, jnp.int32(t_pre), jnp.int32(last),
                   dkey, quant, mask)
    rows = jnp.asarray(rows, jnp.int32)
    if picked is None:
        picked = jnp.zeros((rows.shape[0], 1), jnp.int32)
    return _head(params["final_norm"], params["lm_head"], x, rows,
                 jnp.asarray(picked, jnp.int32), d["eps"], quant,
                 d["mask_id"], 256, full)


def forward_logits(params, d, tokens, rows, quant=None, mask="block"):
    """Reference logits of ONE clean sequence under M.  tokens: [T] int32;
    rows: [R] int32 positions whose logits are wanted.  [R, V] float32."""
    t = len(tokens)
    return forward_rows(params, d, jnp.asarray(tokens, jnp.int32),
                        np.arange(t), np.zeros((t,), np.int32), rows,
                        quant=quant, mask=mask, full=True)


# ----------------------------------------------------------------------
# generation, plainly (a toy's oracle: a whole forward a step, no cache)
# ----------------------------------------------------------------------

def reveal(conf, masked, n):
    """The ``n`` masked rows of highest confidence, ties to the lower
    index (``low_confidence_static``).  numpy, [L] each."""
    order = sorted((i for i in range(len(conf)) if masked[i]),
                   key=lambda i: (-conf[i], i))
    return order[:n]


def generate(params, d, prompt, max_new_tokens, denoise_steps, mask="block"):
    """Greedy generation by diffusion over blocks under the static rule,
    with NO cache: every step forwards the clean sequence so far with the
    open block behind it as it stands.  Returns (tokens, the step that
    revealed each, the logits of every step [blocks, S, L, V])."""
    bl, s_steps = d["block"], denoise_steps
    t0 = len(prompt)
    t_pre = t0 // bl * bl
    tail = t0 - t_pre
    clean = list(prompt[:t_pre])
    out, steps, seen = [], [], []
    nb = -(-(tail + max_new_tokens) // bl)
    for b in range(nb):
        toks = [d["mask_id"]] * bl
        masked = [True] * bl
        step_of = [-1] * bl
        if b == 0:
            toks[:tail] = prompt[t_pre:]
            masked[:tail] = [False] * tail
        per_step = []
        for s in range(s_steps):
            seq = clean + [d["mask_id"] if m else t
                           for t, m in zip(toks, masked)]
            t = len(seq)
            # (padded to the answer's end: ONE compiled forward a request)
            pad = t_pre + nb * bl - t
            kind = [0] * len(clean) + [1] * bl + [-1] * pad
            lg = np.asarray(forward_rows(
                params, d, jnp.asarray(seq + [0] * pad, jnp.int32),
                np.arange(t + pad), np.asarray(kind), np.arange(t - bl, t),
                t_pre=t_pre, last=1, mask=mask, full=True))
            per_step.append(lg)
            lg = lg.copy()
            lg[:, d["mask_id"]] = -1e30
            x0 = lg.argmax(-1)
            top = lg.max(-1)
            conf = 1.0 / np.exp(lg - top[:, None]).sum(-1)
            for i in reveal(conf, masked, bl // s_steps):
                toks[i], masked[i], step_of[i] = int(x0[i]), False, s
        seen.append(np.stack(per_step))
        clean += toks
        first = tail if b == 0 else 0
        out += toks[first:]
        steps += step_of[first:]
    return (list(prompt) + out[:max_new_tokens], steps[:max_new_tokens],
            np.stack(seen))


# ----------------------------------------------------------------------
# the served-model comparison
# ----------------------------------------------------------------------

def block_rows(d, prompt, served, steps, s_steps, t_pad, r_pad):
    """One request as the rows of ONE forward: the clean sequence (prompt
    + the answer's whole blocks), then for every denoising step s the
    answer's whole blocks in their state BEFORE it (a row revealed at a
    step < s, or of the prompt's tail, holds its served token; every
    other row ``[MASK]``), at their own positions; padded to ``t_pad +
    s_steps * r_pad`` rows.  A last block the request's count cut short
    is left out (the engine drops the rows past the count: its state
    cannot be rebuilt).  Returns (tokens, pos, kind, t_pre, and of the
    answer's rows of every copy: their row index [S, n], their block-local
    reveal step [n] (-1: the tail), the served tokens [n])."""
    bl = d["block"]
    t0 = len(prompt)
    t_pre = t0 // bl * bl
    tail = t0 - t_pre
    n = (tail + len(served)) // bl * bl         # rows of whole blocks
    seq = np.asarray(list(prompt) + list(served), np.int32)[:t_pre + n]
    step_of = np.asarray([-1] * tail + list(steps), np.int32)[:n]
    total = t_pad + s_steps * r_pad
    if t_pre + n > t_pad or n > r_pad:
        raise ValueError(f"a request of {t_pre} + {n} rows does not fit "
                         f"the pads ({t_pad}, {r_pad})")
    toks = np.zeros((total,), np.int32)
    pos = np.arange(total, dtype=np.int32)      # pads: positions of their own
    kind = np.full((total,), -1, np.int32)
    toks[:t_pre + n], kind[:t_pre + n] = seq, 0
    at = np.zeros((s_steps, n), np.int32)
    for s in range(s_steps):
        lo = t_pad + s * r_pad
        at[s] = lo + np.arange(n)
        toks[lo:lo + n] = np.where(step_of < s, seq[t_pre:], d["mask_id"])
        pos[lo:lo + n] = t_pre + np.arange(n)
        kind[lo:lo + n] = 1 + s
    return toks, pos, kind, t_pre, at, step_of, seq[t_pre:]


def _gaps(d, ref, step_of, rows_of, col):
    """The two gaps of one request from the reference's readings ``ref``
    (:func:`forward_rows` over the copies' rows, [S * n] each).  rows_of
    [S, n] bool: the rows the judged program revealed at each step; col:
    the column of ``picked`` that holds the logits of the tokens it put
    there.  Served gap, a revealed row: the reference's
    best logit at that row, at that step, less its logit of the token.
    Reveal gap, a step of a block that left rows masked: by the
    reference's own log-confidences, the best row left masked less the
    least row revealed, 0 where the reference would have revealed the
    same rows.  Both over the largest logit magnitude among the compared
    rows."""
    best, _, lse, mag, picked = ref
    s_steps, n = rows_of.shape
    bl = d["block"]
    best, lse, mag = (a.reshape(s_steps, n) for a in (best, lse, mag))
    picked = picked[:, col].reshape(s_steps, n)
    conf = best - lse
    scale = float(mag.max())
    served = ((best - picked) / scale)[rows_of]
    reveal_gaps = []
    for s in range(s_steps):
        masked = (step_of >= s).reshape(-1, bl)
        shown = rows_of[s].reshape(-1, bl)
        c = conf[s].reshape(-1, bl)
        for b in range(masked.shape[0]):
            left = masked[b] & ~shown[b]
            if shown[b].any() and left.any():
                reveal_gaps.append(max(0.0, float(
                    c[b][left].max() - c[b][shown[b]].min())) / scale)
    return served, np.asarray(reveal_gaps)


def block_gaps(params, d, streams, s_steps, t_pad, r_pad, controls=()):
    """For each served request ``(prompt, served_tokens, reveal_steps)``:
    ONE forward of the reference over :func:`block_rows` (teacher-forced:
    every block's state before every step is rebuilt from the SERVED
    tokens and steps, so a flipped choice does not compound), and the two
    gaps of what the program served (:func:`_gaps`).  For each name of
    ``controls`` (:data:`CONTROLS`) the same forward with that mistake
    made, put in the program's place: the tokens it puts first and the
    rows its confidences reveal (as many a step as were served), held
    against the sound reference the same way.  Returns ``{"served":
    {"widest", "mean"}, "reveal": {"widest", "mean"}, "tokens", "steps",
    "per_stream", "controls": {name: {"served": .., "reveal": ..}}}``."""
    bl = d["block"]
    acc = {name: ([], []) for name in ("sound", *controls)}
    per = []
    for prompt, served, steps in streams:
        toks, pos, kind, t_pre, at, step_of, answer = block_rows(
            d, prompt, served, steps, s_steps, t_pad, r_pad)
        n = at.shape[1]
        if not n:
            continue
        rows = at.reshape(-1)
        kw = dict(t_pre=t_pre, last=s_steps)
        # what each judged program put where: the served one first
        shown = {"sound": np.stack([step_of == s for s in range(s_steps)])}
        put = {"sound": np.broadcast_to(answer, (s_steps, n))}
        for name in controls:
            best, arg, lse, _, _ = (np.asarray(a) for a in forward_rows(
                params, d, jnp.asarray(toks), pos, kind, rows, **kw,
                **CONTROLS[name]))
            conf = (best - lse).reshape(s_steps, n)
            rows_c = np.zeros((s_steps, n), bool)
            for s in range(s_steps):
                for b in range(n // bl):
                    sl = slice(b * bl, (b + 1) * bl)
                    picks = reveal(conf[s, sl], step_of[sl] >= s,
                                   int(shown["sound"][s, sl].sum()))
                    rows_c[s, [b * bl + i for i in picks]] = True
            shown[name], put[name] = rows_c, arg.reshape(s_steps, n)
        names = list(shown)
        picked = np.stack([put[k].reshape(-1) for k in names], axis=-1)
        ref = tuple(np.asarray(a) for a in forward_rows(
            params, d, jnp.asarray(toks), pos, kind, rows, picked, **kw))
        for col, name in enumerate(names):
            sg, rg = _gaps(d, ref, step_of, shown[name], col)
            acc[name][0].append(sg)
            acc[name][1].append(rg)
            if name == "sound":
                per.append({
                    "prompt": len(prompt), "served": int(sg.size),
                    "served_widest": float(sg.max()),
                    "served_mean": float(sg.mean()),
                    "argmax_equal": int((sg == 0).sum()),
                    "steps_judged": int(rg.size),
                    "reveal_widest": float(rg.max()) if rg.size else 0.0,
                    "reveal_same": int((rg == 0).sum())})

    def told(name):
        sg = np.concatenate(acc[name][0]) if acc[name][0] else np.zeros(0)
        rg = np.concatenate(acc[name][1]) if acc[name][1] else np.zeros(0)
        stat = lambda g: {"widest": float(g.max()) if g.size else 0.0,
                          "mean": float(g.mean()) if g.size else 0.0}
        return {"served": stat(sg), "reveal": stat(rg),
                "tokens": int(sg.size), "steps": int(rg.size)}

    out = told("sound")
    out["per_stream"] = per
    out["controls"] = {name: told(name) for name in controls}
    return out
