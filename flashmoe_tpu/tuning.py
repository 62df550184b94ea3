"""Per-generation kernel tuning table.

The reference encodes per-architecture execution geometry in a constexpr
trait table — blocks/SM, pipeline stages, tile sizes per (arch, register
budget) (``csrc/include/flashmoe/arch.cuh:95-222``).  The TPU analogue is
this table: measured winners for the Pallas kernels' block sizes keyed by
(generation, kernel, shape), consulted at trace time, with the existing
size-derived heuristics as the fallback when no measurement matches.

No table has been measured yet: the heuristics apply.  A table of
winners measured on real hardware is committed to
``flashmoe_tpu/tuning_data/<gen>.json`` (:func:`save_entries`) so it ships
with the package; entries are ignored with a warning if they stopped
dividing the shapes they claim to match.

Knobs per kernel family:

  capacity_ffn   block_m (row tile), block_i (intermediate chunk) of the
                 grouped capacity-buffer / gather-fused FFN kernels
                 (``ops/expert.py:_capacity_tiling``).
  fused_ep       cm (slab row tile), bi_cap (streamed-weight chunk cap),
                 weights_resident (bool: per-source two-pass schedule),
                 batched (bool: arrival-batched expert-major schedule —
                 overrides the d>=3 default either way), rowwin (bool:
                 row-windowed K-streamed schedule — overrides the
                 stream-vs-rowwin byte heuristic either way) of the
                 fused RDMA kernel (``parallel/fused.py:
                 _fused_schedule``).
  fused_tiles    cm (row tile), kw (K-window width) of the rowwin
                 schedule's IO-aware tile chooser
                 (``parallel/fused.py:_rowwin_tiles``) — a measured
                 entry overrides the analytic minimum-HBM-traffic pick
                 when it still divides the shapes; the VMEM budget gate
                 is never overridable.

Committed tables must pass :func:`validate_entries` — a malformed table
fails ``tests/test_tuning.py`` in CI instead of being silently ignored
at trace time (the runtime ``_load`` stays lenient so a corrupt file on
a production host degrades to heuristics, never to a crash).
"""

from __future__ import annotations

import functools
import json
import os
import warnings

_DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tuning_data")


#: what the planner and the tuning lookups plan FOR when no TPU is
#: attached (tests and planning runs on the CPU): a target, not a reading
PLANNING_TARGET = "v5e"


def generation() -> str:
    """The TPU generation tuning entries and the planner are keyed by:
    ``FLASHMOE_TPU_GEN`` if set, else read from the attached device's
    ``device_kind`` (an unknown kind is an error that names it,
    ``topology.tpu_generation``).  With no TPU attached there is no
    generation to read, and :data:`PLANNING_TARGET` stands."""
    pinned = os.environ.get("FLASHMOE_TPU_GEN")
    if pinned:
        return pinned
    import jax

    from flashmoe_tpu.parallel.topology import tpu_generation

    gen = tpu_generation(jax.devices()[0])
    return PLANNING_TARGET if gen == "cpu" else gen


@functools.lru_cache(maxsize=8)
def _load(gen: str) -> list:
    """Measured entries for a generation: a list of
    ``{"kernel": ..., "match": {...}, "set": {...}, "measured_ms": ...}``
    dicts, most-specific first.  FLASHMOE_TUNING_FILE overrides the
    committed per-generation file."""
    path = os.environ.get("FLASHMOE_TUNING_FILE") or os.path.join(
        _DATA_DIR, f"{gen}.json")
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            doc = json.load(f)
        return list(doc.get("entries", []))
    except (OSError, ValueError) as e:  # unreadable table = no tuning
        warnings.warn(f"ignoring unreadable tuning table {path}: {e}")
        return []


def lookup(kernel: str, gen: str | None = None, **shape) -> dict:
    """Measured knob overrides for ``kernel`` at ``shape`` (h=, i=, cap=,
    dtype=...), or {} when nothing matches.  An entry matches when every
    key in its ``match`` dict equals the corresponding shape value; among
    matches the one constraining the most keys wins regardless of file
    order, so a hand-added generic entry cannot shadow a more specific
    measured one (advisor r4 #3)."""
    gen = gen or generation()
    best = None
    for ent in _load(gen):
        if ent.get("kernel") != kernel:
            continue
        m = ent.get("match", {})
        if all(shape.get(k) == v for k, v in m.items()):
            if best is None or len(m) > len(best[0]):
                best = (m, dict(ent.get("set", {})))
    return best[1] if best else {}


def measured_path_latencies(gen: str | None = None, **shape) -> dict:
    """Measured end-to-end path latencies for ``shape`` (h=, i=, e=, k=,
    s=, d=, dtype=...): ``{path_name: measured_ms}``.

    Entries use ``kernel: "path_latency"`` with the path name inside the
    ``match`` dict (so the generic most-specific-match machinery applies
    per path) and the timing in ``measured_ms``::

        {"kernel": "path_latency",
         "match": {"path": "fused", "h": 2048, "i": 2048, "d": 8},
         "measured_ms": 2.71}

    The ``wire`` / ``wire_combine`` / ``wire_dcn`` keys (EP payload
    compression, ``MoEConfig.wire_dtype`` family — ``wire_dcn`` is the
    cross-slice hop override), the ``chunks`` key (chunked a2a
    pipeline depth, ``MoEConfig.a2a_chunks``), the ``quant`` key
    (quantized expert weight store, ``MoEConfig.expert_quant``) and
    the ``spec`` key (speculative verify span, ``"v<k>"`` for a
    ``verify_tokens=k`` decode measurement) are matched STRICTLY with
    implicit ``"off"`` / ``1`` defaults on both sides: a latency
    measured with compression, chunking, int8 weights, or a
    speculative span on is never applied to a run without it — and a
    legacy entry without the keys never applies to one that has them.

    The planner's measured-winner override
    (:mod:`flashmoe_tpu.planner.select`) consults this: a committed
    measurement beats any prediction for the paths it covers.  Unknown
    shapes return {} and the roofline prediction stands.
    """
    gen = gen or generation()
    best: dict[str, tuple[int, float]] = {}
    for ent in _load(gen):
        if ent.get("kernel") != "path_latency":
            continue
        m = dict(ent.get("match", {}))
        path = m.pop("path", None)
        ms = ent.get("measured_ms", ent.get("set", {}).get("measured_ms"))
        if path is None or ms is None:
            continue
        if any(str(m.pop(wk, dv)) != str(shape.get(wk, dv))
               for wk, dv in (("wire", "off"), ("wire_combine", "off"),
                              ("wire_dcn", "off"), ("chunks", 1),
                              ("quant", "off"), ("spec", "off"))):
            continue
        if all(shape.get(kk) == v for kk, v in m.items()):
            if path not in best or len(m) > best[path][0]:
                best[path] = (len(m), float(ms))
    return {p: ms for p, (_, ms) in best.items()}


#: known kernel families and the knob keys their ``set`` dict may carry
#: (``path_latency`` entries carry the timing in ``measured_ms`` and the
#: path identity inside ``match`` instead of a ``set``)
ENTRY_SCHEMA = {
    "capacity_ffn": {"block_m", "block_i"},
    "fused_ep": {"cm", "bi_cap", "weights_resident", "batched",
                 "rowwin"},
    "fused_tiles": {"cm", "kw"},
    "path_latency": set(),
}

#: keys an entry ``match`` dict may constrain (shape facts + the
#: measurement-identity knobs the lookups compare strictly)
MATCH_KEYS = {"h", "i", "e", "k", "s", "d", "cap", "dtype", "path",
              "wire", "wire_combine", "wire_dcn", "chunks", "quant",
              "spec"}


def validate_entries(doc) -> list[str]:
    """Schema-validate a tuning table document (the parsed JSON of a
    ``tuning_data/<gen>.json`` file).  Returns a list of problem
    strings, empty when the table is well-formed.  CI runs this over
    every committed table (``tests/test_tuning.py``) so a malformed
    entry — unknown kernel, misspelled knob, non-numeric measurement —
    fails review instead of being silently ignored by the lenient
    runtime loader."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return [f"table must be a JSON object, got {type(doc).__name__}"]
    entries = doc.get("entries")
    if not isinstance(entries, list):
        return ["table must carry an 'entries' list"]
    if "generation" in doc and not isinstance(doc["generation"], str):
        problems.append("'generation' must be a string")
    for n, ent in enumerate(entries):
        where = f"entries[{n}]"
        if not isinstance(ent, dict):
            problems.append(f"{where}: entry must be an object")
            continue
        kernel = ent.get("kernel")
        if kernel not in ENTRY_SCHEMA:
            problems.append(
                f"{where}: unknown kernel {kernel!r}; known: "
                f"{sorted(ENTRY_SCHEMA)}")
            continue
        match = ent.get("match", {})
        if not isinstance(match, dict):
            problems.append(f"{where}: 'match' must be an object")
            match = {}
        for mk, mv in match.items():
            if mk not in MATCH_KEYS:
                problems.append(
                    f"{where}: unknown match key {mk!r}; known: "
                    f"{sorted(MATCH_KEYS)}")
            elif mk in ("dtype", "path", "wire", "wire_combine",
                        "wire_dcn", "quant", "spec"):
                if not isinstance(mv, str):
                    problems.append(
                        f"{where}: match.{mk} must be a string, got "
                        f"{mv!r}")
            elif not isinstance(mv, int) or isinstance(mv, bool) \
                    or mv < 1:
                problems.append(
                    f"{where}: match.{mk} must be a positive int, got "
                    f"{mv!r}")
        ms = ent.get("measured_ms",
                     ent.get("set", {}).get("measured_ms")
                     if isinstance(ent.get("set"), dict) else None)
        if kernel == "path_latency":
            if "path" not in match:
                problems.append(
                    f"{where}: path_latency needs match.path")
            if not isinstance(ms, (int, float)) or ms <= 0:
                problems.append(
                    f"{where}: path_latency needs a positive "
                    f"measured_ms, got {ms!r}")
            continue
        st = ent.get("set")
        if not isinstance(st, dict) or not st:
            problems.append(
                f"{where}: {kernel} needs a non-empty 'set' object")
            continue
        allowed = ENTRY_SCHEMA[kernel]
        for sk, sv in st.items():
            if sk not in allowed:
                problems.append(
                    f"{where}: unknown {kernel} knob {sk!r}; known: "
                    f"{sorted(allowed)}")
            elif sk in ("weights_resident", "batched", "rowwin"):
                if not isinstance(sv, bool):
                    problems.append(
                        f"{where}: set.{sk} must be a bool, got {sv!r}")
            elif not isinstance(sv, int) or isinstance(sv, bool) \
                    or sv < 1:
                problems.append(
                    f"{where}: set.{sk} must be a positive int, got "
                    f"{sv!r}")
        if kernel == "fused_tiles" and not {"cm", "kw"} <= set(st):
            problems.append(
                f"{where}: fused_tiles must set both cm and kw (a "
                f"half-specified tile pair cannot override the "
                f"IO-aware chooser)")
        if "measured_ms" in ent and (
                not isinstance(ent["measured_ms"], (int, float))
                or ent["measured_ms"] <= 0):
            problems.append(
                f"{where}: measured_ms must be a positive number, got "
                f"{ent['measured_ms']!r}")
    return problems


def validate_table(path: str) -> list[str]:
    """:func:`validate_entries` over a table file; unreadable/unparsable
    files are themselves a problem (CI-facing — the runtime loader's
    lenient warning stance is unchanged)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"unreadable table {path}: {e}"]
    return validate_entries(doc)


def save_entries(gen: str, entries: list, path: str | None = None) -> str:
    """Write a measured table.  Replaces
    existing entries for the same (kernel, match) keys, keeps others."""
    path = path or os.path.join(_DATA_DIR, f"{gen}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    old = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f).get("entries", [])
        except (OSError, ValueError):
            old = []
    keyof = lambda e: (e.get("kernel"),
                       tuple(sorted(e.get("match", {}).items())))
    new_keys = {keyof(e) for e in entries}
    merged = entries + [e for e in old if keyof(e) not in new_keys]
    with open(path, "w") as f:
        json.dump({"generation": gen, "entries": merged}, f, indent=1,
                  sort_keys=True)
    _load.cache_clear()
    return path
