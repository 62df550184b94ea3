"""Operations and bytes a call NEEDS, computed from shapes.

These are the yardstick's numerators: what the algorithm requires, not
what an implementation happens to do.  Each states what it counts and errs
low, because a share above 105 % of a peak is refused.  ``d`` is the
dictionary ``reference.model_dims`` makes from a configuration file.
"""

from __future__ import annotations

import math

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _n_mats(d) -> int:
    return 3 if d["gated"] else 2


def _is_moe(d, li: int) -> bool:
    return (li + 1) % d["moe_every"] == 0


def capacity(d, tokens: int) -> int:
    """Rows an expert may take: every token where nothing is dropped, else
    ``capacity_factor * top_k * ceil(tokens / experts)`` (at least 8)."""
    if not d["drop_tokens"]:
        return tokens
    return max(8, math.ceil(d["capacity_factor"] * d["top_k"]
                            * math.ceil(tokens / d["experts"])))


def expert_rows(d, tokens: int) -> int:
    """Rows the routed experts must compute for ``tokens`` tokens: one per
    (token, choice), but never more than the capacity lets through."""
    return min(tokens * d["top_k"], d["experts"] * capacity(d, tokens))


def expert_gemm_flops(d, tokens: int) -> float:
    """Forward FLOPs of the ROUTED experts' matrix products alone (what
    the grouped-FFN kernels compute): 2 or 3 products of H x I a row."""
    return _n_mats(d) * 2.0 * expert_rows(d, tokens) * d["hidden"] * d["inter"]


def moe_layer_flops(d, tokens: int) -> float:
    """Forward FLOPs of one mixture layer: the router's product, the
    routed experts (``expert_gemm_flops``) and the shared experts on every
    token (arithmetic of the program's ``analysis.layer_flops``, plus the
    shared experts it leaves out)."""
    gate = 2.0 * tokens * d["hidden"] * d["experts"]
    shared = (_n_mats(d) * 2.0 * tokens * d["hidden"]
              * d["inter"] * d["shared"])
    return gate + expert_gemm_flops(d, tokens) + shared


def dense_ffn_flops(d, tokens: int) -> float:
    return _n_mats(d) * 2.0 * tokens * d["hidden"] * d["inter"]


def model_forward_flops(d, batch: int, seq: int) -> float:
    """Forward FLOPs of the whole model on ``batch`` sequences of ``seq``
    tokens: projections, causal attention (half of the square), the
    feed-forward of each layer and the output head.  Recomputation is not
    counted."""
    tokens = batch * seq
    h, nh, dh = d["hidden"], d["heads"], d["head_dim"]
    proj = 4 * 2.0 * tokens * h * nh * dh
    attn = 2 * 2.0 * batch * nh * dh * seq * (seq + 1) / 2
    total = 0.0
    for li in range(d["layers"]):
        moe = _is_moe(d, li)
        total += proj + attn + (moe_layer_flops(d, tokens) if moe
                                else dense_ffn_flops(d, tokens))
    return total + 2.0 * tokens * h * d["vocab"]


def train_step_flops(d, batch: int, seq: int) -> float:
    """Forward + backward: three times the forward's products."""
    return 3.0 * model_forward_flops(d, batch, seq)


def weight_bytes(d) -> float:
    """Bytes of every weight a decode step must read once: all layers'
    projections, router, routed and shared experts, and the output head
    (the embedding's few rows are left out).  EVERY routed expert is
    counted: at 32 slots x top-6 of 64 a step touches all but about 4 % of
    them (1 - (1 - 6/64)^32), so this is high by at most that share of the
    expert bytes when the batch is full, and by more when it is not."""
    b = _BYTES[d["param_dtype"]]
    h, nh, dh, i = d["hidden"], d["heads"], d["head_dim"], d["inter"]
    per_layer = 4 * h * nh * dh
    total = 0.0
    for li in range(d["layers"]):
        moe = _is_moe(d, li)
        n_exp = d["experts"] if moe else 1
        shared = d["shared"] if moe else 0
        total += per_layer + _n_mats(d) * h * i * (n_exp + shared)
        total += h * n_exp if moe else 0
    return b * (total + h * d["vocab"])


def expected_expert_touch(d, rows: float) -> float:
    """Share of the routed experts that ``rows`` tokens with independent
    uniform top-k choices touch: 1 - (1 - k/E)^rows."""
    return 1.0 - (1.0 - d["top_k"] / d["experts"]) ** rows


def decode_step_bytes(d, active_slots: float, ctx_tokens: float) -> float:
    """Bytes one decode step must read: the weights, with the routed
    experts scaled by the share that ``active_slots`` live rows are
    expected to touch, plus the keys and values of the ``ctx_tokens`` live
    context tokens (summed over the slots) in every layer.  Writes,
    activations and the logits are left out, so the count errs low."""
    b = _BYTES[d["param_dtype"]]
    h, i = d["hidden"], d["inter"]
    touch = expected_expert_touch(d, max(active_slots, 1.0))
    routed = 0.0
    for li in range(d["layers"]):
        if _is_moe(d, li):
            routed += _n_mats(d) * h * i * d["experts"]
    w = weight_bytes(d) - b * routed * (1.0 - touch)
    kv = 2.0 * d["layers"] * ctx_tokens * d["heads"] * d["head_dim"] \
        * _BYTES[d["dtype"]]
    return w + kv
