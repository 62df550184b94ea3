"""The plain reference: the configurations' mathematics in straightforward
``jax.numpy``, float32 at matmul precision "highest", no kernels, no cache,
no batching.  It imports nothing of the program and takes nothing the
program has made: the benchmark makes the weights here, from the seed, and
hands the same arrays to the program and to the reference.

One block serves both configurations (``configs/*.json`` give the sizes):
pre-norm transformer, RoPE (half-split rotation), causal multi-head
attention, then a mixture-of-experts feed-forward: router softmax over the
experts, top-k, the chosen weights renormalised to sum to one, every expert
evaluated on every token and combined through the dense weight matrix, plus
the shared experts.  With ``drop_tokens`` the capacity rule is added (ranked
choice-major then by token, a rank at or past the capacity is dropped, the
surviving weights renormalised).

Departures from the published DeepSeekMoE-16B, which the program cannot
express and the reference therefore follows: every layer is a mixture layer
(``first_k_dense_replace`` 1 as published), and the top-k weights are
renormalised (``norm_topk_prob`` false as published).

``quant="fp8"`` is the CONTROL of the serving cells: both operands of
every linear layer are rounded to float8 e4m3 (scaled to the tensor's
largest magnitude), the step below bfloat16 that would tempt a later PR.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# sizes, read from a configuration file's published keys
# ----------------------------------------------------------------------

def model_dims(config: dict) -> dict:
    """The sizes the reference needs, from ``configs/<name>.json``'s
    ``model`` object, in the source's own key names (a Hugging Face
    ``config.json``'s, or the reference project's ``flashmoe_config.json``'s)."""
    m = config["model"]

    def pick(*names, default=None):
        for n in names:
            if n in m:
                return m[n]
        if default is None:
            raise KeyError(f"configuration lacks one of {names}")
        return default

    heads = pick("num_attention_heads", "num_heads")
    hidden = m["hidden_size"]
    act = pick("hidden_act", default="silu")
    return {
        "hidden": hidden,
        "layers": pick("num_hidden_layers", "num_layers"),
        "heads": heads,
        "head_dim": pick("head_dim", default=hidden // heads),
        "vocab": m["vocab_size"],
        "experts": pick("n_routed_experts", "num_experts"),
        "top_k": pick("num_experts_per_tok", "expert_top_k"),
        "shared": pick("n_shared_experts", default=0),
        "inter": pick("moe_intermediate_size", "intermediate_size"),
        "gated": bool(pick("gated_ffn", default=act == "silu")),
        "act": act,
        "rope_theta": float(pick("rope_theta", default=10000.0)),
        "eps": 1e-6,
        "moe_every": pick("moe_layer_freq", "moe_frequency", default=1),
        "drop_tokens": bool(pick("drop_tokens", default=False)),
        "capacity_factor": float(pick("capacity_factor", default=1.0)),
        "aux_loss_coef": float(pick("aux_loss_coef", default=0.01)),
        "dtype": pick("torch_dtype", default="bfloat16"),
        "param_dtype": config.get("served", {}).get(
            "param_dtype", pick("torch_dtype", default="bfloat16")),
    }


def is_moe_layer(d: dict, li: int) -> bool:
    """Layer ``li`` is a mixture layer when (li + 1) is a multiple of the
    period (the reference project's ``moe_frequency``)."""
    return (li + 1) % d["moe_every"] == 0


# ----------------------------------------------------------------------
# weights, on the device, from the seed, in the type they are served in
# ----------------------------------------------------------------------

def _ffn_params(key, d, n_exp, n_shared, dt):
    h, i = d["hidden"], d["inter"]
    ks = jax.random.split(key, 7)
    nrm = lambda k, shape, fan: (
        jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)).astype(dt)
    p = {
        "gate_w": nrm(ks[0], (h, n_exp), h),
        "w_up": nrm(ks[1], (n_exp, h, i), h),
        "b_up": jnp.zeros((n_exp, i), dt),
        "w_down": nrm(ks[2], (n_exp, i, h), i),
        "b_down": jnp.zeros((n_exp, h), dt),
    }
    if d["gated"]:
        p["w_gate"] = nrm(ks[3], (n_exp, h, i), h)
    if n_shared:
        si = i * n_shared
        p["shared_w_up"] = nrm(ks[4], (h, si), h)
        p["shared_w_down"] = nrm(ks[5], (si, h), si)
        if d["gated"]:
            p["shared_w_gate"] = nrm(ks[6], (h, si), h)
    return p


def seed_key(seed: int, stream: int = 0):
    """A key of the device's own bit generator (``rbg``: some ten times
    quicker than threefry at making gigabytes of weights) from a seed that
    may exceed 32 bits, and a stream number."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def make_params(seed: int, d: dict):
    """The whole model's weights in the tree layout the program's entry
    points take (``embed``, ``final_norm``, ``lm_head``, ``layers`` of
    ``attn_norm/ffn_norm/wq/wk/wv/wo/moe``), made in ONE jitted call on
    the default device.  ``seed`` may exceed 32 bits."""
    dt = jnp.dtype(d["param_dtype"])
    h, nh, dh, v = d["hidden"], d["heads"], d["head_dim"], d["vocab"]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, d["layers"] + 2)
        nrm = lambda k, shape, fan: (
            jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)
        ).astype(dt)
        params = {
            "embed": (jax.random.normal(keys[0], (v, h), jnp.float32)
                      * 0.02).astype(dt),
            "final_norm": jnp.ones((h,), dt),
            "lm_head": nrm(keys[1], (h, v), h),
            "layers": [],
        }
        for li in range(d["layers"]):
            lk = jax.random.split(keys[2 + li], 5)
            moe = is_moe_layer(d, li)
            params["layers"].append({
                "attn_norm": jnp.ones((h,), dt),
                "ffn_norm": jnp.ones((h,), dt),
                "wq": nrm(lk[0], (h, nh * dh), h),
                "wk": nrm(lk[1], (h, nh * dh), h),
                "wv": nrm(lk[2], (h, nh * dh), h),
                "wo": nrm(lk[3], (nh * dh, h), nh * dh),
                "moe": _ffn_params(
                    lk[4], d, d["experts"] if moe else 1,
                    d["shared"] if moe else 0, dt),
            })
        return params

    return build(seed_key(seed))


# ----------------------------------------------------------------------
# the block, plainly
# ----------------------------------------------------------------------

def _q8(x):
    """Round to float8 e4m3 at the tensor's own scale and come back; the
    gradient passes straight through the rounding."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.dot(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: [T, N, D]; rotation pairs dimension j with j + D/2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq      # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name):
    return {"silu": jax.nn.silu, "relu": jax.nn.relu,
            "gelu": jax.nn.gelu}[name]


def attention(layer, x, d, quant=None, q_block=None):
    """Causal self-attention over one sequence x: [T, H] float32.
    ``q_block``: compute the scores in blocks of that many query rows."""
    t = x.shape[0]
    nh, dh = d["heads"], d["head_dim"]
    pos = jnp.arange(t)
    q = _mm(x, layer["wq"], quant).reshape(t, nh, dh)
    k = _mm(x, layer["wk"], quant).reshape(t, nh, dh)
    v = _mm(x, layer["wv"], quant).reshape(t, nh, dh)
    q, k = _rope(q, pos, d["rope_theta"]), _rope(k, pos, d["rope_theta"])

    def rows(qb, pb):
        s = jnp.einsum("tnd,snd->nts", qb, k, precision=HIGHEST) \
            / math.sqrt(dh)
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s, -1e30)
        return jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    if q_block is None or q_block >= t:
        ctx = rows(q, pos)
    else:
        ctx = jax.lax.map(
            lambda qp: jax.checkpoint(rows)(*qp),
            (q.reshape(t // q_block, q_block, nh, dh),
             pos.reshape(t // q_block, q_block))).reshape(t, nh, dh)
    return _mm(ctx.reshape(t, nh * dh), layer["wo"], quant)


def capacity_rows(d, tokens, n_exp):
    """Rows one expert may take: capacity_factor * top_k * ceil(T / E),
    at least 8 (the reference project's ``EC``)."""
    return max(8, math.ceil(d["capacity_factor"] * d["top_k"]
                            * math.ceil(tokens / n_exp)))


def combine_weights(x, gate_w, d, n_exp, with_probs=False):
    """[T, E] dense combine weights: softmax, top-k, capacity rule where
    the configuration drops, renormalised over what survives."""
    k = d["top_k"]
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    keep = jnp.ones(top_p.shape, bool)
    if d["drop_tokens"]:
        t = x.shape[0]
        cap = capacity_rows(d, t, n_exp)
        # rank of each (token, choice) in its expert's queue, choice-major
        flat = top_i.T.reshape(-1)                               # [k*T]
        onehot = jax.nn.one_hot(flat, n_exp, dtype=jnp.int32)
        rank = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(flat.shape[0]), flat]
        keep = (rank < cap).reshape(k, t).T
    w = jnp.where(keep, top_p / jnp.sum(top_p, -1, keepdims=True), 0.0)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-20)
    cw = jnp.einsum("tk,tke->te", w,
                    jax.nn.one_hot(top_i, n_exp, dtype=jnp.float32))
    if with_probs:
        return cw, probs, top_i, logits
    return cw


def _expert(p, e, xe, d, quant):
    act = _act(d["act"])
    up = _mm(xe, p["w_up"][e], quant) + p["b_up"][e].astype(jnp.float32)
    hid = (act(_mm(xe, p["w_gate"][e], quant)) * up if "w_gate" in p
           else act(up))
    return _mm(hid, p["w_down"][e], quant) + p["b_down"][e].astype(jnp.float32)


def moe_ffn(p, x, d, quant=None, with_aux=False):
    """Mixture feed-forward over x: [T, H] float32.  Without drops every
    expert is evaluated on every token; under the capacity rule each
    expert is evaluated on the (at most ``capacity``) tokens it keeps,
    gathered by their combine weight.  One expert (a dense layer) takes
    the weight 1.  ``with_aux`` also returns the router's load-balance
    loss, E * k * sum(share of choices * mean probability)."""
    t = x.shape[0]
    n_exp = p["w_up"].shape[0]
    aux = jnp.zeros((), jnp.float32)
    if n_exp == 1:
        out = _expert(p, 0, x, d, quant)
        return (out, aux) if with_aux else out
    cw, probs, top_i, _ = combine_weights(x, p["gate_w"], d, n_exp, True)
    if with_aux:
        share = jnp.mean(jax.nn.one_hot(top_i, n_exp, dtype=jnp.float32),
                         axis=(0, 1))
        aux = n_exp * d["top_k"] * jnp.sum(share * jnp.mean(probs, axis=0))
    if d["drop_tokens"]:
        cap = min(capacity_rows(d, t, n_exp), t)
        w_e, tok_e = jax.lax.top_k(cw.T, cap)                 # [E, cap]
        tok_c = jax.lax.stop_gradient(tok_e)
        w_e = jnp.take_along_axis(cw.T, tok_c, axis=1)
        y = jax.vmap(lambda e, xe: _expert(p, e, xe, d, quant))(
            jnp.arange(n_exp), x[tok_c])                      # [E, cap, H]
        out = jnp.zeros_like(x).at[tok_c.reshape(-1)].add(
            (w_e[..., None] * y).reshape(-1, x.shape[1]))
    else:
        def one(acc, e):
            return acc + cw[:, e][:, None] * _expert(p, e, x, d, quant), None

        out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_exp))
    if "shared_w_up" in p:
        act = _act(d["act"])
        up = _mm(x, p["shared_w_up"], quant)
        hid = (act(_mm(x, p["shared_w_gate"], quant)) * up
               if "shared_w_gate" in p else act(up))
        out = out + _mm(hid, p["shared_w_down"], quant)
    return (out, aux) if with_aux else out


def _dims_key(d):
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnames=("dkey", "quant"))
def _block(layer, x, dkey, quant):
    d = dict(dkey)
    x = x + attention(layer, _rms(x, layer["attn_norm"], d["eps"]), d, quant)
    return x + moe_ffn(layer["moe"], _rms(x, layer["ffn_norm"], d["eps"]),
                       d, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head, x, rows, eps, quant):
    return _mm(_rms(x[rows], final_norm, eps), lm_head, quant)


def forward_logits(params, d, tokens, rows, quant=None):
    """Reference logits of ONE sequence.  tokens: [T] int32 (padded past
    the true end: causality keeps pads out of earlier rows); rows: [R]
    int32 positions whose logits are wanted.  Layer by layer, so only one
    layer's float32 copies live at a time.  Returns [R, V] float32."""
    x = params["embed"].astype(jnp.float32)[tokens]
    dkey = _dims_key(d)
    for layer in params["layers"]:
        x = _block(layer, x, dkey, quant)
    return _head(params["final_norm"], params["lm_head"], x, rows,
                 d["eps"], quant)


# ----------------------------------------------------------------------
# the served-model comparison
# ----------------------------------------------------------------------

def served_token_gaps(params, d, streams, t_pad, r_pad, control=None):
    """For each served stream ``(prompt, served_tokens)``: run the
    reference once over prompt + served tokens and read, at every served
    position, how far the served token's logit lies below the reference's
    best, as a share of the largest logit magnitude among the compared
    rows.  With ``control`` (a ``quant`` name) the token read at each
    position is instead the one the lower-precision reference puts first.

    Returns ``{"widest": float, "mean": float, "tokens": int,
    "per_stream": [...]}``."""
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
            low = np.asarray(forward_logits(
                params, d, jnp.asarray(toks), jnp.asarray(rows),
                quant=control))[:n]
            picked = low.argmax(-1)
        scale = float(np.abs(ref).max())
        gaps = (ref.max(-1) - ref[np.arange(n), picked]) / scale
        widest = max(widest, float(gaps.max()))
        total += float(gaps.sum())
        count += n
        per.append({"prompt": t0, "served": n,
                    "widest": float(gaps.max()),
                    "mean": float(gaps.mean()),
                    "argmax_equal": int((gaps == 0).sum())})
    return {"widest": widest, "mean": total / max(count, 1),
            "tokens": count, "per_stream": per}


# ----------------------------------------------------------------------
# training: loss, gradients and the optimizer, plainly
# ----------------------------------------------------------------------

def train_loss(params, d, tokens, quant=None, q_block=512, ce_chunks=8):
    """Next-token cross-entropy of tokens [B, T + 1] plus the mixture
    layers' load-balance loss times ``aux_loss_coef``.  Attention runs a
    sequence at a time; the mixture layers see the B * T tokens as one
    shard (sequence-major), as the capacity rule needs; the output head
    and the cross-entropy run in row chunks."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    b, t = inp.shape
    x = params["embed"].astype(jnp.float32)[inp]                 # [B, T, H]
    aux_total = jnp.zeros((), jnp.float32)

    def blk(layer, x):
        a = jax.lax.map(lambda xs: attention(
            layer, _rms(xs, layer["attn_norm"], d["eps"]), d, quant,
            q_block), x)
        x = x + a
        f, aux = moe_ffn(layer["moe"],
                         _rms(x, layer["ffn_norm"], d["eps"]).reshape(b * t, -1),
                         d, quant, with_aux=True)
        return x + f.reshape(b, t, -1), aux

    for layer in params["layers"]:
        x, aux = jax.checkpoint(blk)(layer, x)
        aux_total = aux_total + aux * d.get("aux_loss_coef", 0.01)
    h = _rms(x, params["final_norm"], d["eps"]).reshape(b * t, -1)

    def ce_sum(hc, tc):
        logp = jax.nn.log_softmax(_mm(hc, params["lm_head"], quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[:, None], axis=-1))

    n = b * t
    total = jnp.sum(jax.lax.map(
        lambda ht: jax.checkpoint(ce_sum)(*ht),
        (h.reshape(ce_chunks, n // ce_chunks, -1),
         tgt.reshape(ce_chunks, n // ce_chunks))))
    return total / n + aux_total


def leaf_norms(tree):
    """float32 L2 norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def leaf_sketch(x, j):
    """A short linear sketch of one leaf: its entries times fixed random
    signs (drawn from the leaf's index ``j``, the same for every caller),
    summed over all but the last axis.  Unlike a norm it keeps the
    direction: for two gradients the relative distance of their sketches
    estimates the relative distance of the gradients themselves, which a
    rounding to fewer bits moves by its own size while it moves the norm
    only by the square of that."""
    x = x.astype(jnp.float32)
    sign = jax.random.rademacher(
        jax.random.fold_in(jax.random.key(20240924, impl="rbg"), j),
        x.shape, dtype=jnp.int8)
    y = x * sign
    return y.reshape(-1, x.shape[-1]).sum(0) if x.ndim > 1 else y


def tree_sketches(tree, scale=1.0):
    """:func:`leaf_sketch` of every leaf, in flattening order (host)."""
    return [np.asarray(leaf_sketch(x, j)) * scale
            for j, x in enumerate(jax.tree_util.tree_leaves(tree))]


def sketch_gaps(got, want):
    """The relative distance between the program's sketch of each leaf and
    the reference's, against the reference's sketch norm of that leaf or of
    the median leaf, whichever is larger: the widest (the number compared),
    its leaf, the median leaf's (steadier; printed, decides nothing) and
    all of them."""
    norms = np.asarray([np.linalg.norm(w) for w in want])
    base = np.maximum(norms, np.median(norms))
    gaps = np.asarray([np.linalg.norm(np.asarray(g, np.float64) - w)
                       for g, w in zip(got, want)]) / np.maximum(base, 1e-30)
    return {"worst": float(gaps.max()), "median": float(np.median(gaps)),
            "worst_index": int(gaps.argmax()),
            "per_leaf": [float(g) for g in gaps]}


def make_reference_grad(d, quant=None):
    """(params, tokens) -> (loss, float32 gradients of :func:`train_loss`)."""
    return jax.jit(lambda p, tokens: jax.value_and_grad(train_loss)(
        p, d, tokens, quant))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("b1", "b2", "eps", "wd"))
def _adamw_leaf(w, m, v, g, scale, lr, count, b1, b2, eps, wd):
    """Clip (by the global ``scale``) and AdamW on one leaf."""
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    w = w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w)
    return w, m, v, jnp.sqrt(jnp.sum(g * g))


def reference_train_steps(make_start, d, batches, opt, quant=None):
    """Follow the first ``len(batches)`` steps of training from the
    parameters ``make_start()`` returns, with clip-by-global-norm and
    AdamW as ``opt`` gives them (``lr(step)``, ``b1``, ``b2``, ``eps``,
    ``weight_decay``, ``clip``), all float32.  Returns the losses, the
    per-leaf norms and sketches (:func:`leaf_sketch`) of the first
    (clipped) gradient, and the per-leaf norms of the parameters' change
    after the last step.

    Memory: Adam's moments wait on the HOST between steps and the update
    runs leaf by leaf, so the device never holds more than the
    parameters, one set of gradients and a block's activations; the start
    is made again at the end, not kept."""
    grad = make_reference_grad(d, quant)
    hyper = dict(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                 wd=opt["weight_decay"])
    leaves, treedef = jax.tree_util.tree_flatten(make_start())
    moments = [None] * len(leaves)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        loss, g = grad(jax.tree_util.tree_unflatten(treedef, leaves), tokens)
        g = jax.tree_util.tree_leaves(g)
        gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g)))
        scale = jnp.float32(min(1.0, opt["clip"] / max(gn, 1e-30)))
        lr, count = jnp.float32(opt["lr"](i)), jnp.float32(i + 1)
        gnorms = []
        if first is None:
            sketches = [np.asarray(leaf_sketch(x, j)) * float(scale)
                        for j, x in enumerate(g)]
        for j in range(len(leaves)):
            m, v = moments[j] or (np.zeros(leaves[j].shape, np.float32),) * 2
            leaves[j], m, v, n = _adamw_leaf(
                leaves[j], jnp.asarray(m), jnp.asarray(v), g[j], scale, lr,
                count, **hyper)
            g[j] = None
            moments[j] = (np.asarray(m), np.asarray(v))
            gnorms.append(float(n))
        losses.append(float(loss))
        if first is None:
            first = np.asarray(gnorms)
    del moments, g
    start = jax.tree_util.tree_leaves(make_start())
    delta = np.asarray([float(jnp.sqrt(jnp.sum(jnp.square(a - b))))
                        for a, b in zip(leaves, start)])
    return {"losses": losses, "first_grad_norms": first,
            "first_grad_sketches": sketches, "delta_norms": delta}


def worst_leaf_gap(got, want):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    base = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / np.maximum(base, 1e-30)))


# ----------------------------------------------------------------------
# one mixture layer, rows against rows
# ----------------------------------------------------------------------

def ambiguous_rows(probs, cap, d, tie_tol=1e-3):
    """Rows whose routing a rounding may legitimately change (ported from
    ``chip_smoke.oracle_layer``): two of a token's top-(k+1) router
    probabilities lie within ``tie_tol`` of each other, or one of its
    choices ranks as close to its expert's capacity edge as that expert
    has such tied tokens among its candidates (each of them can move the
    queue behind it by one).  ``probs``: [T, E] float; returns [T] bool."""
    probs = np.asarray(probs, np.float64)
    t, e = probs.shape
    k = d["top_k"]
    order = np.argsort(-probs, axis=1, kind="stable")
    lead = np.take_along_axis(probs, order[:, :k + 1], axis=1)
    tie = ((lead[:, :-1] - lead[:, 1:]) < tie_tol * lead[:, :-1]).any(1)
    if not d["drop_tokens"]:
        return tie
    top_i = order[:, :k]
    flat = top_i.T.reshape(-1)
    srt = np.argsort(flat, kind="stable")
    starts = np.searchsorted(flat[srt], np.arange(e))
    rank = np.empty(t * k, np.int64)
    rank[srt] = np.arange(t * k) - starts[flat[srt]]
    rank = rank.reshape(k, t).T
    slack = np.bincount(order[:, :k + 1][tie].reshape(-1), minlength=e)
    return tie | (np.abs(rank - cap + 0.5) < slack[top_i]).any(1)


@functools.partial(jax.jit, static_argnames=("dkey", "quant"))
def _layer_rows(p, x, dkey, quant):
    d = dict(dkey)
    out = moe_ffn(p, x.astype(jnp.float32), d, quant)
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), p["gate_w"].astype(jnp.float32),
        precision=HIGHEST), axis=-1)
    return out, probs


def layer_row_errors(p, d, x_shards, got_shards, control=None):
    """Each token shard through the plain mixture layer (the capacity rule
    counts within a shard, as an expert-parallel layer applies it rank by
    rank) against the rows the program produced.  Returns the worst row
    error over the unambiguous rows, as a share of the largest reference
    magnitude, and the share of rows set aside as ambiguous.  With
    ``control`` the rows held against the reference are the lower
    precision's own."""
    dkey = _dims_key(d)
    worst, n_amb, n_rows, total = 0.0, 0, 0, 0.0
    for x, got in zip(x_shards, got_shards):
        want, probs = _layer_rows(p, x, dkey, None)
        want = np.asarray(want)
        if control is not None:
            got = np.asarray(_layer_rows(p, x, dkey, control)[0])
        else:
            got = np.asarray(got, np.float32)
        cap = capacity_rows(d, x.shape[0], p["w_up"].shape[0])
        amb = ambiguous_rows(probs, cap, d)
        err = np.max(np.abs(got - want), axis=1) / max(
            float(np.abs(want).max()), 1e-30)
        worst = max(worst, float(err[~amb].max()))
        total += float(err[~amb].sum())
        n_amb += int(amb.sum())
        n_rows += len(amb)
    return {"worst_row_error": worst,
            "mean_row_error": total / max(n_rows - n_amb, 1),
            "ambiguous_share": n_amb / n_rows, "rows": n_rows}
