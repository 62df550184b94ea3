"""Operations and bytes a call NEEDS for the gated-short-convolution
hybrids (``lfm2_24b``), computed from shapes.  ``d`` is the dictionary
``reference_lfm2.model_dims`` makes from a configuration file.

Here seven layers of nine keep the convolution's last inputs a SLOT (8 kB a
layer at H 2048), two keep K and V rows a token, every expert and the whole
vocabulary are held, and the head is the embedding (tied).  Each errs low,
as the siblings do: activations, the logits and the block tables are left
out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def conv_params(d) -> int:
    """Matrix elements of one gated short convolution: W_in (H -> 3 H),
    the taps, W_out."""
    h = d["hidden"]
    return 3 * h * h + d["taps"] * h + h * h


def attn_params(d) -> int:
    """Matrix elements of one grouped-query attention mixer: W_q, W_k,
    W_v, W_o and the two head-wide norms."""
    h, dh = d["hidden"], d["head_dim"]
    return (2 * h * d["heads"] * dh + 2 * h * d["kv_heads"] * dh + 2 * dh)


def expert_params(d) -> int:
    """One routed expert: three H x I matrices."""
    return 3 * d["hidden"] * d["inter"]


def layer_params(d, li: int) -> int:
    """Matrix elements of layer ``li``: its mixer and a dense SwiGLU, or
    its mixer, the router and every expert."""
    mixer = conv_params(d) if d["kinds"][li] == "conv" else attn_params(d)
    if li < d["first_dense"]:
        return mixer + 3 * d["hidden"] * d["dense_inter"]
    return (mixer + d["hidden"] * d["experts"]
            + d["experts"] * expert_params(d))


def model_params(d) -> int:
    """Every matrix of the model as cut: the layers and the embedding,
    which is the output head too (tied)."""
    return (sum(layer_params(d, li) for li in range(d["layers"]))
            + d["vocab"] * d["hidden"])


def kv_token_bytes(d) -> int:
    """Bytes one cached token costs: K and V of every K/V head, in the
    served type, in the attention layers alone."""
    return (d["kinds"].count("full_attention") * 2 * d["kv_heads"]
            * d["head_dim"] * _BYTES[d["param_dtype"]])


def state_slot_bytes(d) -> int:
    """Bytes of state one slot holds, whatever its context: the last
    taps - 1 inputs of the convolution, in every convolution layer."""
    return (d["kinds"].count("conv") * (d["taps"] - 1) * d["hidden"]
            * _BYTES[d["param_dtype"]])


def expected_experts_touched(d, active_slots: float) -> float:
    """Experts a decode step of ``active_slots`` tokens is expected to
    touch in a mixture layer, the rows falling on the experts
    independently and alike: E (1 - (1 - 1/E)^(slots x k))."""
    e = d["experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (active_slots * d["top_k"]))


def decode_step_bytes(d, ctx_tokens: float, slots: int,
                      experts_touched: float | None = None,
                      active_slots: float | None = None) -> float:
    """Bytes one decode step must move: every weight (the embedding once:
    it is the head), the experts scaled by the share of them that the
    step's rows touch (``experts_touched`` a mixture layer, as the
    program's ``serve_decode`` records count it; default: expected from
    ``active_slots``); the K and V rows of the ``ctx_tokens`` live
    context tokens (summed over the slots) in the attention layers, once;
    and the convolution inputs of all ``slots`` slots of the step's batch,
    read once and written once."""
    b = _BYTES[d["param_dtype"]]
    if experts_touched is None:
        experts_touched = expected_experts_touched(
            d, slots if active_slots is None else active_slots)
    moe_layers = d["layers"] - d["first_dense"]
    untouched = moe_layers * (d["experts"] - experts_touched) \
        * expert_params(d)
    return (b * (model_params(d) - untouched)
            + kv_token_bytes(d) * ctx_tokens
            + 2 * slots * state_slot_bytes(d))


def conv_span_flops(d, tokens: int) -> float:
    """FLOPs of the convolution itself over ``tokens`` tokens of one
    sequence, all convolution layers, the projections (weights' work) not
    counted: the gate product B * X, a multiply and an add a tap, the
    gate product C * c, each H wide."""
    per_token = d["hidden"] * (1 + 2 * d["taps"] + 1)
    return d["kinds"].count("conv") * tokens * per_token


def conv_span_bytes(d, tokens: int) -> float:
    """Bytes the convolution must move for ``tokens`` tokens of one
    sequence, all convolution layers: B, C and X in and the gated sum
    out, in the served type, the slot's carried inputs read and written
    once."""
    b = _BYTES[d["param_dtype"]]
    return (d["kinds"].count("conv") * tokens * 4 * d["hidden"] * b
            + 2 * state_slot_bytes(d))
