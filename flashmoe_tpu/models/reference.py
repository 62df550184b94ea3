"""Dense-math reference MoE — the numerical oracle.

The reference repo never finished its correctness oracle: ``rExpert``
(``csrc/correctness/correctness.cuh:19-46``) computes only the gate GEMM +
softmax + argmax.  This module is the complete oracle the CUDA code lacked:
an O(S * E) dense evaluation of the full MoE layer (gate -> softmax -> top-k
-> per-expert FFN -> weighted combine) in plain JAX, used by the test suite
to validate every optimized path (Pallas kernels, capacity-factor dispatch,
EP all-to-all) to tolerance.

It intentionally computes *every* expert for *every* token so routing,
capacity, permutation and communication cannot hide errors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import Activation, MoEConfig


def activation_fn(name: str):
    return {
        Activation.RELU: jax.nn.relu,
        Activation.GELU: jax.nn.gelu,
        Activation.SILU: jax.nn.silu,
        Activation.RELU2: lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def init_moe_params(key, cfg: MoEConfig) -> dict:
    """Random MoE-layer parameters.

    Layout mirrors the reference worker's tensors (``flashmoe/worker.py:56-58``):
    ``gate_w [H, E]``, per-expert up/down projections (+ optional gate proj for
    SwiGLU), all stored stacked on a leading expert axis.
    """
    h, i = cfg.hidden_size, cfg.intermediate_size
    # the router over every output (the zero-compute experts behind the
    # FFN experts), the weights of the FFN experts held here
    e_all, e = cfg.router_width, cfg.experts_held or cfg.num_experts
    ks = jax.random.split(key, 7)
    p = {
        "gate_w": jax.random.normal(ks[0], (h, e_all), cfg.param_dtype) / jnp.sqrt(h),
        "w_up": jax.random.normal(ks[1], (e, h, i), cfg.param_dtype) / jnp.sqrt(h),
        "b_up": jnp.zeros((e, i), cfg.param_dtype),
        "w_down": jax.random.normal(ks[2], (e, i, h), cfg.param_dtype) / jnp.sqrt(i),
        "b_down": jnp.zeros((e, h), cfg.param_dtype),
    }
    if cfg.gated_ffn:
        p["w_gate"] = (
            jax.random.normal(ks[3], (e, h, i), cfg.param_dtype) / jnp.sqrt(h)
        )
    if cfg.intermediate_pad:
        # stored with zero columns beyond the width (cfg.intermediate_pad)
        wide = [(0, 0), (0, 0), (0, cfg.intermediate_pad)]
        for name in ("w_up", "w_gate"):
            if name in p:
                p[name] = jnp.pad(p[name], wide)
        p["b_up"] = jnp.pad(p["b_up"], wide[1:])
        p["w_down"] = jnp.pad(p["w_down"], [wide[0], wide[2], wide[1]])
    if cfg.router_bias and e_all > 1:
        # the selection bias is a buffer the balancing rule moves, not a
        # trained weight: float32, zero until a checkpoint sets it
        p["gate_bias"] = jnp.zeros((e_all,), jnp.float32)
    if cfg.num_shared_experts:
        si = i * cfg.num_shared_experts
        p["shared_w_up"] = (
            jax.random.normal(ks[4], (h, si), cfg.param_dtype) / jnp.sqrt(h)
        )
        p["shared_w_down"] = (
            jax.random.normal(ks[5], (si, h), cfg.param_dtype) / jnp.sqrt(si)
        )
        if cfg.gated_ffn:
            p["shared_w_gate"] = (
                jax.random.normal(ks[6], (h, si), cfg.param_dtype) / jnp.sqrt(h)
            )
    return p


def reference_gate(x, gate_w, cfg: MoEConfig, gate_bias=None):
    """Gate: logits -> softmax over experts -> top-k (or what the
    config's router keys say: sigmoid scores, a selection bias that only
    steers the choice, ``norm_topk_prob``, ``routed_scaling_factor``).

    Returns (combine_weights [S, E], top_idx [S, K], router_probs [S, E],
    aux_loss).  ``combine_weights`` is the softmax prob masked to the top-k
    set and re-normalized to sum to 1 across the chosen experts — matching
    the reference's combine epilogue which divides by the accumulated
    combine-weight sum (``csrc/include/flashmoe/os/processor/processor.cuh``
    combine, and ``TPS`` weight accumulation in ``moe/gate.cuh:678-718``).
    """
    logits = jnp.dot(
        x.astype(cfg.accum_dtype),
        gate_w.astype(cfg.accum_dtype),
        preferred_element_type=cfg.accum_dtype,
    )
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    select = scores if gate_bias is None else scores + gate_bias[None, :]
    if cfg.n_group > 1:
        # group-limited: a group scores the sum of its two best, the
        # topk_group best groups keep their experts in the running
        per = select.reshape(select.shape[0], cfg.n_group, -1)
        score = jnp.sum(jnp.sort(per, axis=-1)[..., -2:], axis=-1)
        floor = jnp.sort(score, axis=-1)[:, -cfg.topk_group][:, None]
        select = jnp.where((score >= floor)[:, :, None], per,
                           -jnp.inf).reshape(select.shape)
    _, top_idx = jax.lax.top_k(select, cfg.expert_top_k)
    top_p = jnp.take_along_axis(scores, top_idx, axis=-1)
    # mask to top-k, renormalize over the selected set
    denom = jnp.sum(top_p, axis=-1, keepdims=True)
    norm_top = (top_p / jnp.maximum(denom, 1e-20) if cfg.norm_topk_prob
                else top_p) * cfg.routed_scaling_factor
    one_hot = jax.nn.one_hot(top_idx, cfg.router_width, dtype=probs.dtype)
    combine_weights = jnp.einsum("sk,ske->se", norm_top, one_hot)

    # Switch-style load-balancing aux loss (gate.cuh:273-299 accumulates
    # mean-logit and mean-expert-count into gML/gMeC -> gL in training mode).
    density = jnp.mean(
        jnp.sum(one_hot, axis=1), axis=0
    )  # fraction routed per expert
    mean_probs = jnp.mean(probs, axis=0)
    aux_loss = cfg.router_width * jnp.sum(density * mean_probs)
    return combine_weights, top_idx, probs, aux_loss


def expert_ffn(x, p, cfg: MoEConfig, e: int):
    """Single-expert FFN: up GEMM -> (+bias) -> act -> down GEMM -> (+bias),
    the same op chain as the fused ``fGET`` pipeline
    (``csrc/include/flashmoe/os/processor/processor.cuh:339-468``)."""
    act = activation_fn(cfg.hidden_act)
    up = jnp.dot(x, p["w_up"][e], preferred_element_type=cfg.accum_dtype)
    up = up + p["b_up"][e].astype(up.dtype)
    if cfg.gated_ffn:
        g = jnp.dot(x, p["w_gate"][e], preferred_element_type=cfg.accum_dtype)
        hidden = act(g) * up
    else:
        hidden = act(up)
    down = jnp.dot(
        hidden.astype(cfg.dtype),
        p["w_down"][e],
        preferred_element_type=cfg.accum_dtype,
    )
    return down + p["b_down"][e].astype(down.dtype)


def shared_expert_ffn(x, p, cfg: MoEConfig):
    act = activation_fn(cfg.hidden_act)
    up = jnp.dot(x, p["shared_w_up"], preferred_element_type=cfg.accum_dtype)
    if cfg.gated_ffn:
        g = jnp.dot(x, p["shared_w_gate"], preferred_element_type=cfg.accum_dtype)
        hidden = act(g) * up
    else:
        hidden = act(up)
    return jnp.dot(
        hidden.astype(cfg.dtype),
        p["shared_w_down"],
        preferred_element_type=cfg.accum_dtype,
    )


def reference_moe(params, x, cfg: MoEConfig):
    """Full dense-math MoE layer.

    x: [S, H] tokens.  Returns (output [S, H], aux_loss).  Every expert is
    evaluated on every token and combined through the dense combine-weight
    matrix, so there is no routing/capacity approximation to compare against.
    Note: with drop_tokens capacity limits, optimized paths may drop tokens
    the oracle keeps; tests account for that explicitly.
    """
    combine_weights, _, _, aux = reference_gate(
        x, params["gate_w"], cfg, params.get("gate_bias"))
    xs = x.astype(cfg.dtype)
    all_out = jnp.stack(
        [expert_ffn(xs, params, cfg, e) for e in range(cfg.num_experts)], axis=0
    )  # [E, S, H]
    out = jnp.einsum(
        "se,esh->sh",
        combine_weights[:, :cfg.num_experts].astype(cfg.accum_dtype),
        all_out.astype(cfg.accum_dtype),
    )
    if cfg.zero_experts:
        # the outputs behind the FFN experts are the identity
        out = out + jnp.sum(
            combine_weights[:, cfg.num_experts:], axis=-1,
            keepdims=True).astype(cfg.accum_dtype) * xs.astype(cfg.accum_dtype)
    if cfg.num_shared_experts:
        out = out + shared_expert_ffn(xs, params, cfg).astype(out.dtype)
    return out.astype(cfg.dtype), aux
