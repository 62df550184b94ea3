"""From the profiler's trace to numbers: the one reduction every PR uses.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.  A
device plane (``/device:TPU:<n>``) carries a line of whole programs
(``XLA Modules``: one event per execution of a jitted program, named
``jit_<function>(<fingerprint>)``) and a line of the operations inside them
(``XLA Ops``).  Host planes carry the threads' spans, the program's
``TraceAnnotation``s among them, on the same clock.

Everything here works on plain lists of ``(name, start_ns, dur_ns)`` so
that a small recorded trace (``tests/trace_small.json``) can check it.
"""

from __future__ import annotations

import glob
import json
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: {line: [(name, start_ns, dur_ns)]}},
    "host": [(name, start_ns, dur_ns)]}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    lines[line.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events)
    return {"devices": devices, "host": host}


def union_ns(events) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(events, t0=None, t1=None):
    """Idle intervals ``(start_ns, dur_ns)`` between the events, inside
    ``[t0, t1]`` (default: first start to last end)."""
    evs = sorted(events, key=lambda e: e[1])
    if not evs:
        return []
    out, end = [], (evs[0][1] if t0 is None else t0)
    for _, start, dur in evs:
        if start > end:
            out.append((end, start - end))
        end = max(end, start + dur)
    if t1 is not None and t1 > end:
        out.append((end, t1 - end))
    return out


def time_by_pattern(events, pattern: str):
    """(total_ns, count) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hit = [dur for name, _, dur in events if rx.search(name)]
    return sum(hit), len(hit)


def totals_by_name(events, strip_id=True):
    """[(name, total_ns, count)] by descending time.  ``strip_id`` folds
    ``jit_f(123)`` into ``jit_f`` so that a fingerprint does not split
    one program's executions."""
    acc = {}
    for name, _, dur in events:
        key = re.sub(r"\(\d+\)$", "", name) if strip_id else name
        t, n = acc.get(key, (0, 0))
        acc[key] = (t + dur, n + 1)
    return sorted(((k, t, n) for k, (t, n) in acc.items()),
                  key=lambda r: -r[1])


def exposed_ns(events, pattern: str) -> int:
    """Time in the events matching ``pattern`` (collectives) during which
    no OTHER event of the list runs on that device."""
    rx = re.compile(pattern)
    comm = [e for e in events if rx.search(e[0])]
    other = [e for e in events if not rx.search(e[0])]
    both = union_ns(comm + other)
    return both - union_ns(other)


def attribute_gaps(gap_list, host_events, min_ns=20_000):
    """Name each idle gap of at least ``min_ns`` after the host span that
    covers most of it (spans over ten times the gap's length are too wide
    to explain it).  Returns [(name, total_seconds)] by descending time."""
    host = sorted(host_events, key=lambda e: e[1])
    starts = [e[1] for e in host]
    import bisect

    acc = {}
    for g0, glen in gap_list:
        if glen < min_ns:
            continue
        g1 = g0 + glen
        best, best_cover = "(no host span)", 0
        hi = bisect.bisect_right(starts, g1)
        for name, s, d in host[max(0, hi - 400):hi]:
            if s + d <= g0 or d > 10 * glen:
                continue
            cover = min(g1, s + d) - max(g0, s)
            if cover > best_cover:
                best, best_cover = name, cover
        acc[best] = acc.get(best, 0) + glen
    return sorted(((k, v / 1e9) for k, v in acc.items()),
                  key=lambda r: -r[1])


def summarize(trace: dict, n_devices: int = 1) -> dict | None:
    """The numbers the result line and the readers need; None where no
    operation ran on a device (a CPU rehearsal)."""
    planes = sorted(trace["devices"])[:n_devices]
    if not planes:
        return None
    busy, windows, per_dev = [], [], {}
    for p in planes:
        lines = trace["devices"][p]
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        mods = lines.get(MODULE_LINE, [])
        evs = ops + mods
        if not evs:
            return None
        t0 = min(e[1] for e in evs)
        t1 = max(e[1] + e[2] for e in evs)
        busy.append(union_ns(ops))
        windows.append(t1 - t0)
        per_dev[p] = {"ops": ops, "modules": mods, "t0": t0, "t1": t1}
    first = per_dev[planes[0]]
    window_ns = max(windows)
    top_ops = [[n, t / 1e9] for n, t, _ in totals_by_name(
        first["ops"], strip_id=False)[:10]]
    idle = attribute_gaps(gaps(first["ops"], first["t0"], first["t1"]),
                          trace["host"])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window_ns / 1e9,
        "modules": [[n, t / 1e9, c]
                    for n, t, c in totals_by_name(first["modules"])],
        "top_ops": top_ops,
        "idle_gaps": [[n, s] for n, s in idle],
        "per_device": per_dev,
    }


def summarize_dir(trace_dir: str, n_devices: int = 1) -> dict | None:
    """Summarize the newest trace under a ``jax.profiler`` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    return summarize(read_xplane(found[-1]), n_devices)


def dump_small(trace: dict, path: str, max_events: int = 400) -> None:
    """Write the trace's first stretch as JSON, every line cut at the same
    instant (where the busiest line reaches ``max_events``): a recording
    small enough to keep beside the tests."""
    cut = min(sorted(e[1] for e in evs)[:max_events][-1]
              for ls in trace["devices"].values() for evs in ls.values()
              if evs)
    keep = lambda evs: [list(e) for e in sorted(evs, key=lambda e: e[1])
                        if e[1] + e[2] <= cut]
    small = {"devices": {p: {ln: keep(evs) for ln, evs in ls.items()}
                         for p, ls in trace["devices"].items()},
             "host": keep(trace["host"])[-max_events:]}
    with open(path, "w") as f:
        json.dump(small, f)
