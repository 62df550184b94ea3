"""Wire-dtype compression for the expert-parallel all-to-all payload.

The EP transports (:mod:`flashmoe_tpu.parallel.ep`,
:mod:`flashmoe_tpu.parallel.ragged_ep`) ship every routed token row at
the compute dtype, so the dispatch/combine exchanges — the term the
analytical model says dominates the collective path
(:mod:`flashmoe_tpu.analysis`) — move 2-4x more ICI/DCN bytes than the
tokens need.  This module is the codec those layers apply at the wire
boundary only: rows are quantized immediately before the exchange and
dequantized immediately after, so every compute stage (gate, dispatch
plan, expert FFN, combine) still runs at the compute dtype.

Two wire families, selected by ``MoEConfig.wire_dtype`` /
``MoEConfig.wire_dtype_combine`` (``None`` = off = bit-identical graphs,
the same convention as ``collect_stats`` / ``degrade_unhealthy_experts``):

``bf16``
    A plain dtype cast — halves f32 payloads, no sidecar.  Lossless for
    the ~8 mantissa bits a routed activation keeps anyway through a bf16
    matmul.
``e4m3`` / ``e5m2`` (``jnp.float8_e4m3fn`` / ``jnp.float8_e5m2``)
    Per-token-row symmetric scaling: each row is divided by
    ``amax(|row|) / finfo(fp8).max`` and cast to fp8; the f32 scale rides
    the exchange as a tiny sidecar array (4 bytes per row next to
    ``H * 1`` payload bytes).  e4m3 keeps 3 mantissa bits (better
    resolution, the default for activations); e5m2 keeps the wider
    exponent for combine-side outputs whose dynamic range survived a
    gate-weighted sum.

Numerical contracts (property-tested in ``tests/test_wire.py``):

* zero rows and zero elements survive the round trip exactly;
* scaling a row by ``c > 0`` scales the decoded row by exactly ``c``
  (the fp8 mantissa pattern is scale-invariant);
* a non-finite input row decodes to a non-finite row — NaN poisons the
  scale, Inf drives it to ``inf`` and the payload to ``0 * inf = NaN``
  — so the tier-0 health mask (:mod:`flashmoe_tpu.ops.health`) still
  trips on the far side of an fp8 wire.

Everything here is ``jnp.where``/cast arithmetic: jit-, vmap- and
shard_map-safe, no collectives, no Python-level data dependence.
"""

from __future__ import annotations

import jax.numpy as jnp

# Canonical wire names -> jnp dtypes.  fp8 types are resolved lazily via
# getattr so the module imports (and bf16 wires work) on jax builds that
# predate float8 support; requesting an fp8 wire there is a config-time
# ValueError, not a mid-trace crash.
_FP8_E4M3 = getattr(jnp, "float8_e4m3fn", None)
_FP8_E5M2 = getattr(jnp, "float8_e5m2", None)

_ALIASES = {
    "bf16": "bf16",
    "bfloat16": "bf16",
    "e4m3": "e4m3",
    "float8_e4m3fn": "e4m3",
    "fp8": "e4m3",          # the activation-friendly default fp8
    "e5m2": "e5m2",
    "float8_e5m2": "e5m2",
}

_DTYPES = {
    "bf16": jnp.bfloat16,
    "e4m3": _FP8_E4M3,
    "e5m2": _FP8_E5M2,
}

WIRE_NAMES = tuple(sorted(_ALIASES))


def canonical_name(name: str | None) -> str:
    """Canonical wire name ('bf16' / 'e4m3' / 'e5m2'), or 'off' for
    ``None`` — the spelling measurement keys use."""
    if name is None:
        return "off"
    key = _ALIASES.get(str(name).lower())
    if key is None:
        raise ValueError(
            f"unknown wire dtype {name!r}; supported: {WIRE_NAMES}")
    return key


def fp8_supported() -> bool:
    """Whether this jax build ships the float8 dtypes."""
    return _FP8_E4M3 is not None and _FP8_E5M2 is not None


def resolve(name: str | None):
    """Wire name -> jnp dtype, or ``None`` for ``None``/'off' (wire off).

    Raises ``ValueError`` for unknown names and for fp8 requests on a
    jax build without float8 dtypes — config validation calls this so
    unsupported wires fail at ``MoEConfig`` construction, never inside
    ``shard_map``."""
    if name is None:
        return None
    key = canonical_name(name)
    if key == "off":
        return None
    dt = _DTYPES[key]
    if dt is None:
        raise ValueError(
            f"wire dtype {name!r} needs float8 support this jax build "
            f"lacks; use wire_dtype='bf16' or None")
    return dt


def is_fp8(wire_dtype) -> bool:
    """True for the scaled fp8 wires (payload rides with a scale
    sidecar); False for plain-cast wires (bf16) and None."""
    if wire_dtype is None:
        return False
    return jnp.dtype(wire_dtype).itemsize == 1


def scale_bytes(wire_dtype) -> int:
    """Per-row sidecar bytes the wire adds next to the payload: 4 (one
    f32 scale) for fp8 wires, 0 otherwise.  The byte model
    (:mod:`flashmoe_tpu.analysis`) and the planner price this."""
    return 4 if is_fp8(wire_dtype) else 0


def payload_row_bytes(wire_dtype, h: int, compute_dtype) -> float:
    """Bytes of ONE token row's wire *payload* (scale sidecar excluded):
    ``H x wire itemsize``, or ``H x compute itemsize`` when the wire is
    off.  ``analysis.wire_row_bytes`` adds :func:`scale_bytes` on top;
    the collective census (:mod:`flashmoe_tpu.staticcheck.census`) needs
    the two terms separately because payload and sidecar ride separate
    ``all_to_all`` eqns in the lowered graph."""
    dt = compute_dtype if wire_dtype is None else wire_dtype
    return float(h * jnp.dtype(dt).itemsize)


def encode(x, wire_dtype):
    """Quantize ``x`` (``[..., H]``, rows on the last axis) for the wire.

    Returns ``(payload, scales)``: ``payload`` has ``x``'s shape at the
    wire dtype; ``scales`` is a ``[...]`` f32 array of per-row factors
    for fp8 wires, ``None`` for plain-cast wires (nothing extra to
    exchange).
    """
    if not is_fp8(wire_dtype):
        return x.astype(wire_dtype), None
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    fmax = jnp.float32(jnp.finfo(wire_dtype).max)
    # All-zero rows keep scale 1.0 (0/1 -> 0 exactly).  A NaN amax skips
    # the where's true-branch (NaN > 0 is False) but the payload cast
    # still carries the NaN elements; an Inf amax makes scale=inf and
    # payload 0/NaN, and the decode's 0 * inf = NaN marks the whole row
    # — either way non-finite rows stay non-finite across the wire.
    scale = jnp.where(amax > 0, amax / fmax, jnp.float32(1.0))
    payload = (xf / scale).astype(wire_dtype)
    return payload, scale[..., 0]


def decode(payload, scales, out_dtype):
    """Invert :func:`encode`: ``(payload, scales)`` -> ``[..., H]`` at
    ``out_dtype``.  ``scales=None`` is the plain-cast arm."""
    if scales is None:
        return payload.astype(out_dtype)
    return (payload.astype(jnp.float32)
            * scales[..., None].astype(jnp.float32)).astype(out_dtype)


def roundtrip(x, wire_dtype):
    """encode+decode without an exchange — what the far side would see."""
    payload, scales = encode(x, wire_dtype)
    return decode(payload, scales, x.dtype)


def roundtrip_error(x, wire_dtype) -> jnp.ndarray:
    """Mean relative L1 quantization error of the wire on ``x`` (f32
    scalar): ``sum|x - rt(x)| / (sum|x| + eps)``.  The in-graph proxy
    ``MoEStats.wire_rtq_error`` reports so the flight recorder sees how
    lossy the wire is on live traffic (0.0 when the wire is off)."""
    xf = x.astype(jnp.float32)
    rt = roundtrip(xf, wire_dtype).astype(jnp.float32)
    num = jnp.sum(jnp.abs(xf - rt))
    den = jnp.sum(jnp.abs(xf)) + jnp.float32(1e-9)
    return (num / den).astype(jnp.float32)
