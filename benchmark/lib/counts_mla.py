"""Operations and bytes a call NEEDS for the latent-attention
configurations (``joyai_flash``), computed from shapes.  ``d`` is the
dictionary ``reference_mla.model_dims`` makes from a configuration file.

``lib/counts.py`` describes a cache of ``2 x heads x head_dim`` elements a
token a layer and one feed-forward width; pointed at this model it would
count seven times the bytes the latent pool holds.  These are the same
numerators for a cache of ``kv_lora_rank + qk_rope_head_dim`` elements a
token a layer, a leading dense layer of its own width, and the absorbed
decode.  Each errs low, as there: writes, activations, the gathered copy
of the context and the logits are left out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mla_params(d) -> int:
    """Matrix elements of one layer's attention: W_qa, W_qb, W_kva, W_kvb,
    W_o (the two small norms are left out)."""
    h, nh = d["hidden"], d["heads"]
    dn, dr, dv = d["d_nope"], d["d_rope"], d["d_v"]
    return (h * d["q_rank"] + d["q_rank"] * nh * (dn + dr)
            + h * (d["kv_rank"] + dr) + d["kv_rank"] * nh * (dn + dv)
            + nh * dv * h)


def expert_params(d) -> int:
    """One routed (or shared) expert: three H x I matrices."""
    return 3 * d["hidden"] * d["inter"]


def layer_params(d, li: int) -> int:
    """Matrix elements of layer ``li``: attention and a dense SwiGLU, or
    attention, the router, every routed expert and the shared experts."""
    if li < d["first_dense"]:
        return mla_params(d) + 3 * d["hidden"] * d["dense_inter"]
    return (mla_params(d) + d["hidden"] * d["experts"]
            + (d["experts"] + d["shared"]) * expert_params(d))


def model_params(d) -> int:
    """Every matrix of the model as cut: the layers, the embedding and
    the output head."""
    return (sum(layer_params(d, li) for li in range(d["layers"]))
            + 2 * d["vocab"] * d["hidden"])


def latent_token_bytes(d) -> int:
    """Bytes one cached token costs over all layers: the latent beside
    the shared rotary key, in the served type."""
    return (d["layers"] * (d["kv_rank"] + d["d_rope"])
            * _BYTES[d["param_dtype"]])


def expected_expert_touch(d, rows: float) -> float:
    """Share of the routed experts that ``rows`` tokens with independent
    uniform top-k choices touch: 1 - (1 - k/E)^rows."""
    return 1.0 - (1.0 - d["top_k"] / d["experts"]) ** rows


def decode_step_bytes(d, active_slots: float, ctx_tokens: float) -> float:
    """Bytes one decode step must read: every weight but the embedding
    (whose few rows are left out), the routed experts scaled by the share
    that ``active_slots`` live rows are expected to touch, plus the latent
    rows of the ``ctx_tokens`` live context tokens (summed over the slots)
    in every layer, once."""
    b = _BYTES[d["param_dtype"]]
    moe_layers = d["layers"] - d["first_dense"]
    routed = moe_layers * d["experts"] * expert_params(d)
    touch = expected_expert_touch(d, max(active_slots, 1.0))
    weights = model_params(d) - d["vocab"] * d["hidden"] \
        - routed * (1.0 - touch)
    return b * weights + latent_token_bytes(d) * ctx_tokens


def absorbed_attention_flops(d, active_slots: float,
                             ctx_tokens: float) -> float:
    """FLOPs of the absorbed attention of one decode step over all
    layers: a slot's query folded into the latent space (heads x d_nope x
    rank), the scores over rank + d_rope and the output over rank for
    every live context token and head, and the output unfolded (heads x
    rank x d_v).  The projections are weights' work and not counted."""
    nh, rank = d["heads"], d["kv_rank"]
    per_slot = 2.0 * nh * rank * (d["d_nope"] + d["d_v"])
    per_ctx = 2.0 * nh * (2 * rank + d["d_rope"])
    return d["layers"] * (active_slots * per_slot + ctx_tokens * per_ctx)
