"""Operations and bytes a call NEEDS for the hybrid linear-attention
configurations (``ling3_flash``), computed from shapes.  ``d`` is the
dictionary ``reference_ling3.model_dims`` makes from a configuration file.

``lib/counts.py`` and ``lib/counts_mla.py`` describe one kind of layer and
a cache that grows a token a layer.  Here six layers of seven keep a state
of constant size a SLOT, one keeps latent rows a token, and the chip holds
a share of the experts.  Each errs low, as there: activations, the
gathered copy of the context, the convolution's inputs and the logits are
left out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def kda_params(d) -> int:
    """Matrix elements of one delta-rule mixer: W_qkv, the decay's W_a,
    the two per-head gates W_b and W_g, the convolution's taps, W_o."""
    h, nd = d["hidden"], d["heads"] * d["kda_dim"]
    return (3 * h * nd + h * nd + 2 * h * d["heads"] + d["conv"] * 3 * nd
            + nd * h)


def mla_params(d) -> int:
    """Matrix elements of one latent-attention mixer with queries
    projected direct: W_q, W_kva, W_kvb, W_o."""
    h, nh = d["hidden"], d["heads"]
    dn, dr, dv = d["d_nope"], d["d_rope"], d["d_v"]
    return (h * nh * (dn + dr) + h * (d["kv_rank"] + dr)
            + d["kv_rank"] * nh * (dn + dv) + nh * dv * h)


def expert_params(d) -> int:
    """One routed (or shared) expert: three H x I matrices."""
    return 3 * d["hidden"] * d["inter"]


def layer_params(d, li: int) -> int:
    """Matrix elements of layer ``li`` HELD HERE: its mixer and a dense
    SwiGLU, or its mixer, the router over every published expert, the
    experts held and the shared experts."""
    mixer = kda_params(d) if d["kinds"][li] == "kda" else mla_params(d)
    if li < d["first_dense"]:
        return mixer + 3 * d["hidden"] * d["dense_inter"]
    return (mixer + d["hidden"] * d["router_experts"]
            + (d["experts"] + d["shared"]) * expert_params(d))


def model_params(d) -> int:
    """Every matrix of the model as cut: the layers, the embedding and
    the output head over the vocabulary's slice."""
    return (sum(layer_params(d, li) for li in range(d["layers"]))
            + 2 * d["vocab"] * d["hidden"])


def latent_token_bytes(d) -> int:
    """Bytes one cached token costs: the latent beside the shared rotary
    key, in the served type, in the latent-attention layers alone."""
    return (d["kinds"].count("mla") * (d["kv_rank"] + d["d_rope"])
            * _BYTES[d["param_dtype"]])


def state_slot_bytes(d) -> int:
    """Bytes of recurrent state one slot holds, whatever its context: a
    float32 [heads, D, D] state and the convolution's last taps - 1
    inputs of q, k and v, in every delta-rule layer."""
    n, dk = d["heads"], d["kda_dim"]
    return d["kinds"].count("kda") * (
        n * dk * dk * 4
        + (d["conv"] - 1) * 3 * n * dk * _BYTES[d["param_dtype"]])


def expected_held_rows(d, active_slots: float) -> float:
    """Routed rows of ``active_slots`` tokens expected on the experts held
    here, a mixture layer: top-k times the share of the experts held
    (routing groups chosen alike)."""
    return active_slots * d["top_k"] * d["experts"] / d["router_experts"]


def expected_expert_touch(d, held_rows: float) -> float:
    """Share of the experts held that ``held_rows`` routed rows, falling
    on them independently and alike, touch: 1 - (1 - 1/held)^rows."""
    return 1.0 - (1.0 - 1.0 / d["experts"]) ** held_rows


def decode_step_bytes(d, active_slots: float, ctx_tokens: float,
                      slots: int, held_rows: float | None = None) -> float:
    """Bytes one decode step must move: every weight held but the
    embedding (whose few rows are left out), the held experts scaled by
    the share that ``held_rows`` rows (default: expected from
    ``active_slots``) touch; the latent rows of the ``ctx_tokens`` live
    context tokens (summed over the slots) in the latent-attention
    layers, once; and the recurrent state of all ``slots`` slots of the
    step's batch, read once and written once."""
    b = _BYTES[d["param_dtype"]]
    moe_layers = d["layers"] - d["first_dense"]
    routed = moe_layers * d["experts"] * expert_params(d)
    if held_rows is None:
        held_rows = expected_held_rows(d, max(active_slots, 1.0))
    touch = expected_expert_touch(d, held_rows)
    weights = model_params(d) - d["vocab"] * d["hidden"] \
        - routed * (1.0 - touch)
    return (b * weights + latent_token_bytes(d) * ctx_tokens
            + 2 * slots * state_slot_bytes(d))


def kda_chunk_flops(d, tokens: int, chunk: int = 64) -> float:
    """FLOPs of the chunkwise delta rule over ``tokens`` tokens of one
    sequence, all delta-rule layers, the projections (weights' work) not
    counted.  A head a chunk of C tokens: the two C x C decay-weighted
    products A and B (2 x 2 C^2 D), the unit-triangular solve for
    [U0 | W] (C^2 x 2 D), and against the state W S, Q S and K^T U
    (3 x 2 C D^2) and B U (2 C^2 D)."""
    n, dk = d["heads"], d["kda_dim"]
    per_chunk = (4 * chunk * chunk * dk + 2 * chunk * chunk * dk
                 + 6 * chunk * dk * dk + 2 * chunk * chunk * dk)
    return d["kinds"].count("kda") * n * (tokens / chunk) * per_chunk


def kda_chunk_bytes(d, tokens: int) -> float:
    """Bytes the chunkwise form must move for ``tokens`` tokens of one
    sequence, all delta-rule layers: q, k, v and the log decays in
    float32 in, the heads' outputs in float32 out, the slot's state read
    and written once."""
    n, dk = d["heads"], d["kda_dim"]
    return (d["kinds"].count("kda") * tokens * n * dk * 4 * 5
            + 2 * state_slot_bytes(d))
