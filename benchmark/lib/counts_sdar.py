"""Operations and bytes a call NEEDS for the block-diffusion mixture models
(``sdar_30b_a3b``), computed from shapes.  ``d`` is the dictionary
``reference_sdar.model_dims`` makes from a configuration file.

Every layer is alike (grouped-query attention and a mixture), every expert
and the whole vocabulary are held, the head is an array of its own.  A
DENOISE step forwards a block of ``d["block"]`` rows a slot: the weights
are read once whatever the rows, the experts by the share of them that the
step's rows touch, the K/V pool by each slot's context, and the block's own
rows are written in place.  Each errs low, as the siblings do: activations,
the float32 logits ([rows, V]: 0.16 GB at 256 rows, written and read by
the argmax and the confidences) and the block tables are left out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(d) -> int:
    """Matrix elements of one attention mixer: W_q, W_k, W_v, W_o and the
    two head-wide norms."""
    h, dh = d["hidden"], d["head_dim"]
    return 2 * h * d["heads"] * dh + 2 * h * d["kv_heads"] * dh + 2 * dh


def expert_params(d) -> int:
    """One routed expert: three H x I matrices."""
    return 3 * d["hidden"] * d["inter"]


def layer_params(d) -> int:
    """One layer: its mixer, the router, every expert."""
    return (attn_params(d) + d["hidden"] * d["experts"]
            + d["experts"] * expert_params(d))


def model_params(d) -> int:
    """Every matrix of the model as cut: the layers, the embedding and
    the head (untied)."""
    return d["layers"] * layer_params(d) + 2 * d["vocab"] * d["hidden"]


def kv_token_bytes(d) -> int:
    """Bytes one cached token costs over the layers: K and V of every K/V
    head in the served type."""
    return (d["layers"] * 2 * d["kv_heads"] * d["head_dim"]
            * _BYTES[d["param_dtype"]])


def expected_experts_touched(d, rows: float) -> float:
    """Experts a span of ``rows`` rows is expected to touch in a layer,
    the routed rows falling on the experts independently and alike."""
    e = d["experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (rows * d["top_k"]))


def denoise_step_bytes(d, ctx_tokens: float, slots: int,
                       experts_touched: float | None = None) -> float:
    """Bytes one launch of the denoise program must move: the attention
    and router weights of every layer, the experts the step's
    ``slots x block`` rows touch (``experts_touched`` a layer, as the
    program counts them; expected if None), the head, ONE row of the
    embedding a fed row, the K/V rows of ``ctx_tokens`` cached tokens
    (all slots together) and the block's own rows written."""
    b = _BYTES[d["param_dtype"]]
    rows = slots * d["block"]
    if experts_touched is None:
        experts_touched = expected_experts_touched(d, rows)
    weights = d["layers"] * (attn_params(d) + d["hidden"] * d["experts"]
                             + experts_touched * expert_params(d))
    weights += d["vocab"] * d["hidden"] + rows * d["hidden"]
    return weights * b + (ctx_tokens + rows) * kv_token_bytes(d)


def denoise_step_flops(d, ctx_tokens: float, slots: int) -> float:
    """Operations of one launch: 2 a multiply-add over the matrices every
    row passes (attention, router, top-k experts, head) and the scores
    and weighted sums of each slot's block over its context and itself."""
    rows = slots * d["block"]
    per_row = (attn_params(d) + d["hidden"] * d["experts"]
               + d["top_k"] * expert_params(d))
    dense = 2.0 * rows * (d["layers"] * per_row + d["vocab"] * d["hidden"])
    seen = ctx_tokens * d["block"] + rows * d["block"]
    return dense + d["layers"] * 4.0 * d["heads"] * d["head_dim"] * seen


def paged_decode_bytes(d, ctx_tokens: float, slots: int) -> float:
    """Bytes ONE ``fm_paged_decode`` call (a layer) must move at a span of
    ``block`` rows a slot: the K and V rows of the slots' contexts, the
    page each block is written into read and written back, the queries in
    and the heads' outputs out."""
    b = _BYTES[d["param_dtype"]]
    row = 2 * d["kv_heads"] * d["head_dim"] * b
    rows = slots * d["block"]
    return (ctx_tokens * row + 2 * slots * 16 * row
            + 2 * rows * d["heads"] * d["head_dim"] * b)


def chunk_flops(d, tokens: int, ctx_rows: int) -> float:
    """Operations of one prefill chunk of ``tokens`` rows over a context
    of ``ctx_rows`` (no head: a model that generates by blocks reads no
    next-token logits; the program still computes ONE row of it)."""
    per_row = (attn_params(d) + d["hidden"] * d["experts"]
               + d["top_k"] * expert_params(d))
    return (2.0 * tokens * d["layers"] * per_row
            + d["layers"] * 4.0 * d["heads"] * d["head_dim"]
            * tokens * ctx_rows / 2 + 2.0 * d["vocab"] * d["hidden"])


def chunk_bytes(d, tokens: int, ctx_rows: int) -> float:
    """Bytes one prefill chunk must move: every weight of the layers
    (4096 routed rows touch every expert), the head, the context's K/V
    rows read and the chunk's written."""
    b = _BYTES[d["param_dtype"]]
    return ((d["layers"] * layer_params(d) + d["vocab"] * d["hidden"]) * b
            + (ctx_rows + tokens) * kv_token_bytes(d))
