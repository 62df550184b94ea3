"""Operations and bytes a call NEEDS for ``granite4_h_micro``
(granite-4.0-h-micro, the whole model), computed from shapes.  ``d`` is the
dictionary ``reference_granite4.model_dims`` makes from a configuration
file.

Every layer is a mixer AND a dense part: a 'mamba' layer keeps a float32
state [n, P, N] and the convolution's last inputs a SLOT (2.12 MB a layer),
an 'attention' layer K and V rows of eight heads of 64 a token (2 kB a
layer); the head is the embedding's own array.  Each errs low, as the
siblings do: activations, the logits and the block tables are left out.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _inner(d) -> int:
    return d["m_heads"] * d["m_head_dim"]


def _conv_width(d) -> int:
    return _inner(d) + 2 * d["groups"] * d["state"]


def ssm_params(d) -> int:
    """Elements of one state-space mixer: W_in (H -> z, xBC, dt), the taps
    and their bias, dt_bias, A_log and D a head, the gated norm's weight,
    W_out."""
    h, di, n = d["hidden"], _inner(d), d["m_heads"]
    return (h * (2 * di + 2 * d["groups"] * d["state"] + n)
            + (d["taps"] + 1) * _conv_width(d) + 3 * n + di + di * h)


def attn_params(d) -> int:
    """Elements of one grouped-query attention mixer: W_q, W_k, W_v, W_o."""
    h, dh = d["hidden"], d["head_dim"]
    return 2 * h * d["heads"] * dh + 2 * h * d["kv_heads"] * dh


def dense_params(d) -> int:
    """Elements of one dense SwiGLU part: W_g, W_u, W_out."""
    return 3 * d["hidden"] * d["inter"]


def layer_params(d, li: int) -> int:
    """Elements of layer ``li``: its mixer, its dense part, its two
    norms."""
    mixer = ssm_params if d["kinds"][li] == "mamba" else attn_params
    return mixer(d) + dense_params(d) + 2 * d["hidden"]


def model_params(d) -> int:
    """Every array of the model: the layers, the embedding (the head too:
    counted ONCE) and the final norm."""
    return (sum(layer_params(d, li) for li in range(d["layers"]))
            + d["vocab"] * d["hidden"] + d["hidden"])


def kv_token_bytes(d) -> int:
    """Bytes one cached token costs: K and V of every K/V head, in the
    served type, in the attention layers alone."""
    return (d["kinds"].count("attention") * 2 * d["kv_heads"]
            * d["head_dim"] * _BYTES[d["param_dtype"]])


def state_bytes(d) -> int:
    """Bytes of float32 state ONE 'mamba' layer keeps of one slot."""
    return d["m_heads"] * d["m_head_dim"] * d["state"] * 4


def state_slot_bytes(d) -> int:
    """Bytes one slot holds, whatever its context: the float32 state and
    the last taps - 1 inputs of the convolution (served type), in every
    'mamba' layer."""
    return d["kinds"].count("mamba") * (
        state_bytes(d)
        + (d["taps"] - 1) * _conv_width(d) * _BYTES[d["param_dtype"]])


def ssm_step_bytes(d, state_rows: int) -> float:
    """Bytes ONE ``fm_ssm_step`` launch must move: the float32 state of
    every row the program streams, read once and written once."""
    return 2.0 * state_rows * state_bytes(d)


def paged_decode_bytes(d, ctx_tokens: float) -> float:
    """Bytes ONE ``fm_paged_decode`` launch must move: the K and V rows of
    the ``ctx_tokens`` live context tokens (summed over the slots) of ONE
    attention layer, once."""
    return (2.0 * d["kv_heads"] * d["head_dim"] * _BYTES[d["param_dtype"]]
            * ctx_tokens)


def decode_step_bytes(d, ctx_tokens: float, state_rows: int) -> float:
    """Bytes one decode step must move: every weight once (the embedding
    whole: the tied head reads every row of it), the K and V rows of the
    ``ctx_tokens`` live context tokens in the attention layers, once, and
    the state and convolution inputs of the ``state_rows`` rows the program
    streams, read once and written once."""
    return (_BYTES[d["param_dtype"]] * model_params(d)
            + kv_token_bytes(d) * ctx_tokens
            + 2 * state_rows * state_slot_bytes(d))


def chunk_flops_by_part(d, tokens: int, ctx_tokens: int | None = None,
                        chunk: int = 256) -> dict:
    """FLOPs of a span of ``tokens`` tokens of ONE sequence through every
    layer (a prefill chunk; the head on one row is left out), by part:
    ``products`` (the mixers' projections and the dense parts),
    ``chunked_form`` (the state-space mixer's masked products inside chunks
    of ``chunk`` tokens and the two products with the state, which the
    program computes in float32 at "highest": several passes of the matrix
    unit each, counted ONCE here) and ``flash_span`` (causal attention of
    the span over ``ctx_tokens`` context tokens, default the span itself:
    only the blocks at or under the diagonal)."""
    h, t = d["hidden"], tokens
    ctx = tokens if ctx_tokens is None else ctx_tokens
    di, n, p, g, ns = (_inner(d), d["m_heads"], d["m_head_dim"],
                       d["groups"], d["state"])
    k = d["kinds"]
    dense = 2 * t * dense_params(d)
    ssm_proj = 2 * t * h * (2 * di + 2 * g * ns + n) + 2 * t * di * h
    attn_proj = 2 * t * attn_params(d)
    form = (2 * t * chunk * g * ns           # C . B inside a chunk
            + 2 * t * chunk * n * p          # the masked products
            + 2 * 2 * t * n * p * ns)        # with the state, in and out
    flash = 2 * 2 * t * (ctx - t / 2) * d["heads"] * d["head_dim"]
    return {"products": (d["layers"] * dense + k.count("mamba") * ssm_proj
                         + k.count("attention") * attn_proj),
            "chunked_form": k.count("mamba") * form,
            "flash_span": k.count("attention") * flash}


def chunk_flops(d, tokens: int, ctx_tokens: int | None = None) -> float:
    """The sum of :func:`chunk_flops_by_part`."""
    return sum(chunk_flops_by_part(d, tokens, ctx_tokens).values())


def flash_span_flops(d, tokens: int, ctx_tokens: int) -> float:
    """FLOPs of ONE ``fm_flash_span`` launch (one attention layer)."""
    return (chunk_flops_by_part(d, tokens, ctx_tokens)["flash_span"]
            / d["kinds"].count("attention"))


def chunk_bytes(d, tokens: int, ctx_tokens: int | None = None) -> float:
    """Bytes a span of ``tokens`` tokens of ONE sequence must move: every
    layer's weights once (of the embedding the span's rows and, for the
    one row the head scores, all of it), the slot's state in and out, the
    K/V rows of the context read once and the span's written."""
    ctx = tokens if ctx_tokens is None else ctx_tokens
    return (_BYTES[d["param_dtype"]] * (model_params(d)
                                        + tokens * d["hidden"])
            + 2 * state_slot_bytes(d) + kv_token_bytes(d) * (ctx + tokens))
