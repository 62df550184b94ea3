"""Weight-quantization codec: symmetric absmax per-output-channel (and
optional per-K-group) int8 / fp8-e4m3, f32 scales, dequant-in-compute.

Layout convention (matches ``models/reference.py:init_moe_params``):
every expert FFN weight is ``[..., K, N]`` with the contraction (K)
axis second-to-last and the OUTPUT channels (N) last — ``w_up`` /
``w_gate`` are ``[E, H, I]`` (channels = I), ``w_down`` is ``[E, I, H]``
(channels = H).  Scales therefore reduce over K: shape ``[..., 1, N]``
per-channel, or ``[..., K // g, N]`` with a K-group size ``g``.

Numerical contracts (property-tested in ``tests/test_quant.py``):

* zero channels survive the round trip exactly (scale pinned to 1.0);
* scaling a channel by ``c > 0`` scales the decoded channel by exactly
  ``c`` (the mantissa pattern is scale-invariant);
* int8 payloads are clipped to ``[-127, 127]`` (symmetric — no -128,
  so negation round-trips);
* accumulation dtype is untouched: dequant produces f32 (cast to the
  compute dtype by the caller), so the matmul's
  ``preferred_element_type=f32`` path is byte-identical to the
  full-precision kernel's.

Everything here is cast/round/`jnp.where` arithmetic: jit-, vmap- and
shard_map-safe, no collectives — the same hygiene bar as
:mod:`flashmoe_tpu.ops.wire`.
"""

from __future__ import annotations

import jax.numpy as jnp

# fp8 resolved lazily so the module imports on jax builds without
# float8 support; requesting the e4m3 store there is a config-time
# ValueError, never a mid-trace crash (the ops/wire.py convention).
_FP8_E4M3 = getattr(jnp, "float8_e4m3fn", None)

_ALIASES = {
    "int8": "int8",
    "i8": "int8",
    "e4m3": "e4m3",
    "float8_e4m3fn": "e4m3",
    "fp8": "e4m3",  # the weight-friendly fp8 (3 mantissa bits)
}

QUANT_NAMES = tuple(sorted(_ALIASES))

#: symmetric int8 range: +-127 (no -128, so q -> -q is exact)
_INT8_QMAX = 127.0


def canonical_name(name: str | None) -> str:
    """Canonical store name ('int8' / 'e4m3'), or 'off' for ``None`` —
    the spelling measurement keys and golden tables use."""
    if name is None:
        return "off"
    key = _ALIASES.get(str(name).lower())
    if key is None:
        raise ValueError(
            f"unknown expert_quant dtype {name!r}; supported: "
            f"{QUANT_NAMES}")
    return key


def resolve(name: str | None):
    """Store name -> payload jnp dtype, or ``None`` for off.  Raises
    ``ValueError`` for unknown names and for e4m3 on a jax build
    without float8 dtypes — config validation calls this so
    unsupported stores fail at ``MoEConfig`` construction."""
    if name is None:
        return None
    key = canonical_name(name)
    if key == "off":
        return None
    if key == "int8":
        return jnp.int8
    if _FP8_E4M3 is None:
        raise ValueError(
            f"expert_quant={name!r} needs float8 support this jax "
            f"build lacks; use expert_quant='int8' or None")
    return _FP8_E4M3


def weight_itemsize(name: str | None, compute_dtype) -> float:
    """Bytes ONE expert-weight element occupies on the HBM stream:
    1 for both quantized stores, the compute itemsize when quant is
    off.  The byte model (:mod:`flashmoe_tpu.analysis`) and the fused
    kernel's tile geometry (``parallel/fused.py:schedule_table``) both
    price weights through this one function, so the model can never
    disagree with the codec about what actually streams."""
    if name is None:
        return float(jnp.dtype(compute_dtype).itemsize)
    canonical_name(name)  # validate
    return 1.0


def scale_overhead_bytes(name: str | None, n_channels: int,
                         n_groups: int = 1) -> float:
    """Bytes of the f32 scale sidecar riding next to a quantized
    matrix: one f32 per (K-group, output channel), 0 when quant is
    off."""
    if name is None:
        return 0.0
    return 4.0 * n_channels * max(n_groups, 1)


def _qmax(qdtype) -> jnp.ndarray:
    if jnp.dtype(qdtype) == jnp.int8:  # staticcheck: ok static store dtype — host metadata, never a tracer
        return jnp.float32(_INT8_QMAX)
    return jnp.float32(jnp.finfo(qdtype).max)


def _check_group(k: int, group_size: int | None) -> int:
    g = int(group_size) if group_size else k
    if g < 1 or k % g:
        raise ValueError(
            f"quant group_size={group_size} must divide the "
            f"contraction dim K={k}")
    return g


def quantize_channelwise(w, qname: str, *, group_size: int | None = None,
                         clip=None):
    """Quantize ``w`` (``[..., K, N]``) to the ``qname`` store.

    Returns ``(payload, scales)``: ``payload`` has ``w``'s shape at the
    store dtype; ``scales`` is ``[..., K // g, N]`` f32 (``g = K``
    per-channel when ``group_size`` is None).  ``clip`` (optional,
    broadcastable to the scale shape) caps the absmax per channel —
    the percentile-calibration hook (:mod:`flashmoe_tpu.quant.
    calibrate`); values beyond the clip saturate at the clip point.
    """
    qd = resolve(qname)
    if qd is None:
        raise ValueError("cannot quantize with expert_quant off")
    *lead, k, n = w.shape
    g = _check_group(k, group_size)
    wf = w.astype(jnp.float32).reshape(*lead, k // g, g, n)
    amax = jnp.max(jnp.abs(wf), axis=-2)              # [..., K//g, N]
    if clip is not None:
        amax = jnp.minimum(amax, jnp.asarray(clip, jnp.float32))
    qmax = _qmax(qd)
    # all-zero channels keep scale 1.0 (0 / 1 -> 0 exactly)
    scale = jnp.where(amax > 0, amax / qmax, jnp.float32(1.0))
    scaled = wf / scale[..., None, :]
    if jnp.dtype(qd) == jnp.int8:  # staticcheck: ok static store dtype — host metadata, never a tracer
        payload = jnp.clip(jnp.round(scaled), -_INT8_QMAX,
                           _INT8_QMAX).astype(jnp.int8)
    else:
        payload = jnp.clip(scaled, -qmax, qmax).astype(qd)
    return payload.reshape(w.shape), scale


def dequantize_channelwise(payload, scales, out_dtype=jnp.float32):
    """Invert :func:`quantize_channelwise`: ``(payload [..., K, N],
    scales [..., G, N])`` -> f32 (or ``out_dtype``) weights.  The group
    size is inferred from the shapes, so a stored state carries its
    grouping in the scale array itself — no side-channel metadata
    needed to decode."""
    *lead, k, n = payload.shape
    gcount = scales.shape[-2]
    if gcount < 1 or k % gcount:
        raise ValueError(
            f"scale groups {gcount} do not divide K={k}")
    g = k // gcount
    wf = payload.astype(jnp.float32).reshape(*lead, gcount, g, n)
    wf = wf * scales[..., None, :].astype(jnp.float32)
    return wf.reshape(payload.shape).astype(out_dtype)


def roundtrip(w, qname: str, *, group_size: int | None = None,
              clip=None):
    """quantize + dequantize without storing — what the dequant-in-
    compute matmul would see.  This IS the in-graph fake-quant arm of
    ``ffn_compute_params`` (full-precision params under
    ``expert_quant``), so the A/B numerics of the knob match offline
    quantization exactly."""
    payload, scales = quantize_channelwise(w, qname,
                                           group_size=group_size,
                                           clip=clip)
    return dequantize_channelwise(payload, scales, w.dtype)


def roundtrip_error(w, qname: str, *,
                    group_size: int | None = None) -> jnp.ndarray:
    """Mean relative L1 quantization error of the store on ``w`` (f32
    scalar): ``sum|w - rt(w)| / (sum|w| + eps)`` — the
    ``MoEStats.quant_error`` proxy (the weight-space analogue of
    ``ops/wire.roundtrip_error``)."""
    wf = w.astype(jnp.float32)
    rt = roundtrip(wf, qname, group_size=group_size)
    num = jnp.sum(jnp.abs(wf - rt))
    den = jnp.sum(jnp.abs(wf)) + jnp.float32(1e-9)
    return (num / den).astype(jnp.float32)
