"""Operations and bytes a call NEEDS for the shortcut-connected
latent-attention configuration (``longcat_flash_omni``), computed from
shapes.  ``d`` is the dictionary ``reference_longcat.model_dims`` makes
from a configuration file.

A PUBLISHED layer is two latent-attention sublayers, two dense SwiGLU
FFNs and one mixture behind a router over FFN experts and identity
experts; a chip holds a share of the FFN experts and every identity
expert costs nothing.  Each function errs low, as the siblings' do:
writes, activations, block tables and the logits are left out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mla_params(d) -> int:
    """Matrix elements of one attention sublayer: W_qa, W_qb, W_kva,
    W_kvb, W_o (the two small norms are left out)."""
    h, nh = d["hidden"], d["heads"]
    dn, dr, dv = d["d_nope"], d["d_rope"], d["d_v"]
    return (h * d["q_rank"] + d["q_rank"] * nh * (dn + dr)
            + h * (d["kv_rank"] + dr) + d["kv_rank"] * nh * (dn + dv)
            + nh * dv * h)


def dense_ffn_params(d) -> int:
    """One dense SwiGLU: three H x ``dense_inter`` matrices."""
    return 3 * d["hidden"] * d["dense_inter"]


def expert_params(d) -> int:
    """One FFN expert: three H x ``inter`` matrices."""
    return 3 * d["hidden"] * d["inter"]


def router_width(d) -> int:
    """Outputs of a router: the published FFN experts and the identity
    experts."""
    return d["router_experts"] + d["zero"]


def layer_dense_params(d) -> int:
    """A published layer without its experts: two attention sublayers,
    two dense FFNs, the router."""
    return (2 * mla_params(d) + 2 * dense_ffn_params(d)
            + d["hidden"] * router_width(d))


def model_params(d) -> int:
    """Every matrix of the model as cut: the layers with the FFN experts
    held here, the embedding and the output head."""
    return (d["layers"] * (layer_dense_params(d)
                           + d["experts"] * expert_params(d))
            + 2 * d["vocab"] * d["hidden"])


def latent_token_bytes(d) -> int:
    """Bytes one cached token costs over all sublayers: the latent beside
    the shared rotary key, in the served type (the pool stores the row
    padded to whole lanes: 640 elements for 576)."""
    return (2 * d["layers"] * (d["kv_rank"] + d["d_rope"])
            * _BYTES[d["param_dtype"]])


def expected_expert_touch(d, rows: float) -> float:
    """Share of the FFN experts held here that ``rows`` tokens with
    independent uniform top-k choices over the router's width touch:
    1 - (1 - k / width)^rows (10.1 of 16 at 64 rows, top-12 of 768)."""
    return 1.0 - (1.0 - d["top_k"] / router_width(d)) ** rows


def decode_step_bytes(d, active_slots: float, ctx_tokens: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read: every dense weight and the head
    (the embedding's few rows are left out), the FFN experts held here
    that the step touches (``experts_touched`` a layer, counted; default:
    what ``active_slots`` rows are expected to touch), plus the latent
    rows of the ``ctx_tokens`` live context tokens (summed over the
    slots) in every sublayer, once."""
    b = _BYTES[d["param_dtype"]]
    if experts_touched is None:
        experts_touched = d["experts"] * expected_expert_touch(
            d, max(active_slots, 1.0))
    weights = (d["layers"] * (layer_dense_params(d)
                              + experts_touched * expert_params(d))
               + d["vocab"] * d["hidden"])
    return b * weights + latent_token_bytes(d) * ctx_tokens


def ffn_stream_bytes(d, experts_touched: float) -> float:
    """Bytes ONE ``fm_ffn_fwd`` launch must read: the three matrices of
    every expert it touches."""
    return _BYTES[d["param_dtype"]] * experts_touched * expert_params(d)


def latent_decode_bytes(d, ctx_tokens: float) -> float:
    """Bytes ONE ``fm_latent_decode`` call must read: a latent row as
    stored for every live context token of the batch."""
    return (d["kv_rank"] + d["d_rope"]) * _BYTES[d["param_dtype"]] \
        * ctx_tokens


def latent_decode_flops(d, ctx_tokens: float) -> float:
    """FLOPs of ONE ``fm_latent_decode`` call: for every head and live
    context token the score over rank + d_rope and the weighted sum over
    rank (the folding of the query and the unfolding of the output are
    the projections' work)."""
    return 2.0 * d["heads"] * (2 * d["kv_rank"] + d["d_rope"]) * ctx_tokens


def prefill_chunk_flops(d, tokens: int, ctx_tokens: float,
                        held_rows: float | None = None) -> float:
    """FLOPs of one prefill program of ``tokens`` rows whose queries see
    ``ctx_tokens`` context rows each in the mean (the chunk's own rows
    included, the causal half counted as seen): two per matrix element a
    row for the dense weights and the head's one row; the decompression
    of the context's K and V a sublayer; the scores and sums; the FFN
    experts over ``held_rows`` routed rows a layer (default: the rows
    ``tokens`` uniform choices put on the experts held)."""
    h, nh = d["hidden"], d["heads"]
    dn, dr, dv, rank = d["d_nope"], d["d_rope"], d["d_v"], d["kv_rank"]
    if held_rows is None:
        held_rows = tokens * d["top_k"] * d["experts"] / router_width(d)
    per_layer = (2.0 * tokens * layer_dense_params(d)
                 + 2.0 * held_rows * expert_params(d))
    decompress = 2.0 * ctx_tokens * rank * nh * (dn + dv)
    attend = 2.0 * tokens * ctx_tokens * nh * (dn + dr + dv)
    return (d["layers"] * (per_layer + 2 * (decompress + attend))
            + 2.0 * h * d["vocab"])


def prefill_chunk_bytes(d, ctx_tokens: float,
                        experts_touched: float | None = None) -> float:
    """Bytes one prefill program must read: the dense weights and the
    head once, the experts held that it touches (default: all of them),
    the context's latent rows once a sublayer."""
    b = _BYTES[d["param_dtype"]]
    if experts_touched is None:
        experts_touched = d["experts"]
    return (b * (d["layers"] * (layer_dense_params(d)
                                + experts_touched * expert_params(d))
                 + d["vocab"] * d["hidden"])
            + latent_token_bytes(d) * ctx_tokens)
