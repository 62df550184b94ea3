"""Operations and bytes a call NEEDS for the state-space hybrids
(``nemotron3_nano``), computed from shapes.  ``d`` is the dictionary
``reference_nemotron3.model_dims`` makes from a configuration file.

Here a layer is ONE thing: an ``M`` layer keeps a float32 state [n, P, N]
and the convolution's last inputs a SLOT (2.13 MB a layer at the published
sizes), a ``*`` layer keeps K and V rows of two heads a token (1 kB), an
``E`` layer holds a share of the experts and routes over all of them; the
head is untied.  Each errs low, as the siblings do: activations, the
logits and the block tables are left out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _inner(d) -> int:
    return d["m_heads"] * d["m_head_dim"]


def _conv_width(d) -> int:
    return _inner(d) + 2 * d["groups"] * d["state"]


def ssm_params(d) -> int:
    """Matrix elements of one state-space mixer: W_in (H -> z, xBC, dt),
    the taps and their bias, dt_bias, A_log and D a head, the gated
    norm's weight, W_out."""
    h, di, n = d["hidden"], _inner(d), d["m_heads"]
    return (h * (2 * di + 2 * d["groups"] * d["state"] + n)
            + (d["taps"] + 1) * _conv_width(d) + 3 * n + di + di * h)


def attn_params(d) -> int:
    """Matrix elements of one grouped-query attention mixer: W_q, W_k,
    W_v, W_o."""
    h, dh = d["hidden"], d["head_dim"]
    return 2 * h * d["heads"] * dh + 2 * h * d["kv_heads"] * dh


def expert_params(d) -> int:
    """One routed expert: two H x I matrices (no gate matrix)."""
    return 2 * d["hidden"] * d["inter"]


def expert_stored(d) -> int:
    """One routed expert as STORED: the two matrices ``inter_stored`` wide
    (zero columns beyond the width): what a step streams and the chip
    holds."""
    return 2 * d["hidden"] * d["inter_stored"]


def mixture_params(d, experts: float | None = None) -> float:
    """One mixture layer as stored: the router over all experts and its
    bias, ``experts`` of the experts held (default: all of them), the
    shared expert."""
    held = d["experts"] if experts is None else experts
    return (d["hidden"] * d["router_experts"] + d["router_experts"]
            + held * expert_stored(d) + 2 * d["hidden"] * d["shared_inter"])


def layer_params(d, li: int) -> int:
    """Matrix elements of layer ``li``: its one part and its one norm."""
    part = {"M": ssm_params, "*": attn_params,
            "E": mixture_params}[d["kinds"][li]](d)
    return part + d["hidden"]


def model_params(d) -> int:
    """Every array of the model as cut: the layers, the embedding, the
    final norm and the untied head."""
    return (sum(layer_params(d, li) for li in range(d["layers"]))
            + 2 * d["vocab"] * d["hidden"] + d["hidden"])


def kv_token_bytes(d) -> int:
    """Bytes one cached token costs: K and V of every K/V head, in the
    served type, in the attention layers alone."""
    return (d["kinds"].count("*") * 2 * d["kv_heads"] * d["head_dim"]
            * _BYTES[d["param_dtype"]])


def state_bytes(d) -> int:
    """Bytes of float32 state ONE ``M`` layer keeps of one slot."""
    return d["m_heads"] * d["m_head_dim"] * d["state"] * 4


def state_slot_bytes(d) -> int:
    """Bytes one slot holds, whatever its context: the float32 state and
    the last taps - 1 inputs of the convolution (served type), in every
    ``M`` layer."""
    return d["kinds"].count("M") * (
        state_bytes(d)
        + (d["taps"] - 1) * _conv_width(d) * _BYTES[d["param_dtype"]])


def held_experts_touched(d, held_rows: float) -> float:
    """Experts HELD here that a span whose ``held_rows`` routed rows fell
    on them is expected to touch in a mixture layer, the rows falling on
    the held experts independently and alike: E_h (1 - (1 - 1/E_h)^rows)."""
    e = d["experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** held_rows)


def ffn_stream_bytes(d, experts_touched: float) -> float:
    """Bytes ONE launch of the routed-rows kernel must stream: the two
    matrices of each held expert that has a row (the rows themselves are
    left out)."""
    return experts_touched * expert_stored(d) * _BYTES[d["param_dtype"]]


def state_step_bytes(d, state_rows: int) -> float:
    """Bytes ONE ``M`` layer's decode step must move: the float32 state of
    every row the program streams, read once and written once."""
    return 2.0 * state_rows * state_bytes(d)


def decode_step_bytes(d, ctx_tokens: float, state_rows: int,
                      held_rows: float | None = None,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must move: every weight once (embedding rows
    left in: they err low against the whole array only by what 256 rows
    miss), the held experts scaled by the share of them that the step's
    rows touch (``experts_touched`` of the held ones a mixture layer, or
    expected from ``held_rows``, the routed rows that fell here, as the
    program's ``serve_decode`` records count them; default: every held
    expert); the K and V rows of the ``ctx_tokens`` live context tokens
    (summed over the slots) in the attention layers, once; and the state
    and convolution inputs of the ``state_rows`` rows the program streams,
    read once and written once."""
    b = _BYTES[d["param_dtype"]]
    if experts_touched is None:
        experts_touched = (d["experts"] if held_rows is None
                           else held_experts_touched(d, held_rows))
    untouched = d["kinds"].count("E") * (d["experts"] - experts_touched) \
        * expert_stored(d)
    # the embedding is read a row a slot, not whole
    weights = model_params(d) - untouched - d["vocab"] * d["hidden"] \
        + state_rows * d["hidden"]
    return (b * weights + kv_token_bytes(d) * ctx_tokens
            + 2 * state_rows * state_slot_bytes(d))


def chunk_flops(d, tokens: int, ctx_tokens: int | None = None) -> float:
    """FLOPs of a span of ``tokens`` tokens of ONE sequence through every
    layer (a prefill chunk; the head on one row is left out): the
    projections, the state-space mixer's chunked form in chunks of 128
    (the masked products inside a chunk and the two products with the
    state), causal attention over ``ctx_tokens`` context tokens (default:
    the span itself) and top-k of the held experts' share of the routed
    rows (expected: held / router of them) plus the shared expert."""
    h, t = d["hidden"], tokens
    ctx = tokens if ctx_tokens is None else ctx_tokens
    di, n, p, g, ns = (_inner(d), d["m_heads"], d["m_head_dim"],
                       d["groups"], d["state"])
    chunk = 128
    ssm = (2 * t * h * (2 * di + 2 * g * ns + n) + 2 * t * di * h
           + 2 * t * chunk * g * ns          # C . B inside a chunk
           + 2 * t * chunk * n * p           # the masked products
           + 2 * 2 * t * n * p * ns)         # with the state, in and out
    dh = d["head_dim"]
    attn = (2 * t * h * (2 * d["heads"] + 2 * d["kv_heads"]) * dh
            + 2 * 2 * t * (ctx - t / 2) * d["heads"] * dh)
    share = d["experts"] / d["router_experts"]
    mix = (2 * t * h * d["router_experts"]
           + t * d["top_k"] * share * 2 * expert_params(d)
           + 2 * t * 2 * h * d["shared_inter"])
    k = d["kinds"]
    return k.count("M") * ssm + k.count("*") * attn + k.count("E") * mix


def chunk_bytes(d, tokens: int, ctx_tokens: int | None = None) -> float:
    """Bytes a span of ``tokens`` tokens of ONE sequence must move: every
    weight once (every held expert: a 1024-token chunk routes 3072 rows
    here over 64 experts), the slot's state in and out, the K/V rows of
    the context read once and the span's written."""
    ctx = tokens if ctx_tokens is None else ctx_tokens
    b = _BYTES[d["param_dtype"]]
    weights = model_params(d) - d["vocab"] * d["hidden"] \
        + tokens * d["hidden"]
    return (b * weights + 2 * state_slot_bytes(d)
            + kv_token_bytes(d) * (ctx + tokens))
