"""Operations and bytes a call NEEDS for the window / full attention
mixtures (``trinity_large``), computed from shapes.  ``d`` is the
dictionary ``reference_trinity.model_dims`` makes from a configuration file.

Here four layers of five see the last ``window`` keys alone and keep their
K/V rows in a page pool of their own, one layer sees every key, a share of
the experts is held behind a router of all of them, and the vocabulary is a
slice.  Each errs low, as the siblings do: activations, the logits, the
block tables and the pages' rounding up to whole blocks are left out.  The
kernels' counts are what ``PERF.md`` section 5 divides a traced call's time
into (``fm_paged_decode``, ``fm_flash_span``, ``fm_ffn_fwd``; window and
full layers apart).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(d) -> int:
    """Matrix elements of one attention layer: W_q, W_g and W_o (H x N D
    each), W_k and W_v (H x N_kv D each) and the two head-wide norms."""
    h, dh = d["hidden"], d["head_dim"]
    return 3 * h * d["heads"] * dh + 2 * h * d["kv_heads"] * dh + 2 * dh


def expert_params(d) -> int:
    """One expert, routed or shared: three H x I matrices."""
    return 3 * d["hidden"] * d["inter"]


def layer_params(d, li: int) -> int:
    """Matrix elements of layer ``li`` as held: its attention, its four
    norms, and a dense SwiGLU, or the router over ALL published experts,
    the experts held and the shared one."""
    own = attn_params(d) + 4 * d["hidden"]
    if li < d["first_dense"]:
        return own + 3 * d["hidden"] * d["dense_inter"]
    return (own + d["hidden"] * d["router_experts"]
            + (d["experts"] + 1) * expert_params(d))


def model_params(d) -> int:
    """Every matrix of the model as cut: the layers, the embedding's slice
    and the head's."""
    return (sum(layer_params(d, li) for li in range(d["layers"]))
            + 2 * d["vocab"] * d["hidden"])


def kv_token_bytes(d) -> int:
    """Bytes one cached token costs in ONE layer: K and V of every K/V
    head, in the served type."""
    return 2 * d["kv_heads"] * d["head_dim"] * _BYTES[d["param_dtype"]]


def window_keys(d, context: int) -> int:
    """Keys a query at the END of a context of ``context`` tokens sees in
    a window layer (itself among them)."""
    return min(context, d["window"])


def paged_decode_bytes(d, contexts, sliding: bool) -> float:
    """Bytes ONE ``fm_paged_decode`` call (one layer, one step) must read:
    the K and V rows each slot's query sees, ``contexts`` being the slots'
    context lengths: all of a context in a full layer, its last
    ``window`` keys in a window layer."""
    seen = [window_keys(d, c) if sliding else c for c in contexts]
    return kv_token_bytes(d) * float(sum(seen))


def flash_span_flops(d, span: int, start: int, sliding: bool) -> float:
    """FLOPs ONE ``fm_flash_span`` call (one layer, one chunk of ``span``
    queries whose first stands at position ``start``) must do: q k^T and
    p v, 2 x 2 x D a (query, key) pair a head, over the pairs the mask
    KEEPS: the triangle and the rectangle before it in a full layer, of
    which a window layer keeps ``window`` keys a query."""
    # queries whose keys still grow (all of them in a full layer), then
    # those that see a whole window
    grow = span if not sliding else min(max(d["window"] - start - 1, 0), span)
    pairs = (grow * start + grow * (grow + 1) / 2.0
             + (span - grow) * d["window"])
    return 4.0 * d["heads"] * d["head_dim"] * pairs


def expected_experts_touched(d, active_slots: float) -> float:
    """HELD experts a decode step of ``active_slots`` tokens is expected
    to touch in a mixture layer, the routed rows falling on the
    ``router_experts`` published experts independently and alike."""
    e = d["router_experts"]
    return d["experts"] * (1.0 - (1.0 - 1.0 / e)
                           ** (active_slots * d["top_k"]))


def ffn_fwd_bytes(d, experts_touched: float) -> float:
    """Bytes ONE ``fm_ffn_fwd`` launch (one mixture layer's routed rows)
    must stream: the three matrices of every held expert a row reached."""
    return (experts_touched * expert_params(d)
            * _BYTES[d["param_dtype"]])


def decode_step_bytes(d, contexts, experts_touched: float | None = None
                      ) -> float:
    """Bytes one decode step must move: every weight held, the routed
    experts scaled by the share a step's rows touch (``experts_touched``
    held experts a mixture layer, as the program's ``serve_decode``
    records count them over all published; default: expected from the
    slots), and the K/V rows every layer's queries see."""
    b = _BYTES[d["param_dtype"]]
    if experts_touched is None:
        experts_touched = expected_experts_touched(d, len(contexts))
    moe_layers = d["layers"] - d["first_dense"]
    untouched = (moe_layers * (d["experts"] - experts_touched)
                 * expert_params(d))
    kinds = d["kinds"]
    return (b * (model_params(d) - untouched)
            + sum(paged_decode_bytes(d, contexts,
                                     k == "sliding_attention")
                  for k in kinds))


def prefill_chunk_flops(d, span: int, start: int) -> float:
    """FLOPs one chunk of ``span`` tokens at position ``start`` must do:
    two a weight a token for the attention, the router, the dense part or
    the ``top_k`` routed (a held share of them: ``experts`` of
    ``router_experts``) and the shared expert, the head for ONE row, and
    the attention's scores and sums."""
    weights = 0.0
    for li in range(d["layers"]):
        weights += attn_params(d)
        if li < d["first_dense"]:
            weights += 3 * d["hidden"] * d["dense_inter"]
        else:
            held = d["top_k"] * d["experts"] / d["router_experts"]
            weights += (d["hidden"] * d["router_experts"]
                        + (held + 1) * expert_params(d))
    return (2.0 * span * weights + 2.0 * d["hidden"] * d["vocab"]
            + sum(flash_span_flops(d, span, start,
                                   k == "sliding_attention")
                  for k in d["kinds"]))
