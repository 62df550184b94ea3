#!/usr/bin/env python3
"""Look at one profiler trace by hand: planes, lines, the names that take
most time; optionally write the small recording the tests keep.

    python3 benchmark/tests/inspect_trace.py <trace-dir-or-xplane.pb> [--dump out.json]
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "lib"))
import trace_reduce as tr  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--dump")
    ap.add_argument("--grep", help="also list every XLA Ops name matching "
                    "this pattern, with its total time")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))[-1]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for name, t, n in tr.totals_by_name(evs)[:12]:
                print(f"      {t / 1e6:10.3f} ms  x{n:<6d} {name[:100]}")
    if args.grep:
        import re

        full = tr.read_xplane(path)
        for plane, lines in full["devices"].items():
            hit = [e for e in lines.get(tr.OP_LINE, [])
                   if re.search(args.grep, e[0])]
            print("GREP", plane, args.grep)
            for name, t, n in tr.totals_by_name(
                    [(re.sub(r"\.\d+ = .*", "", e[0]), e[1], e[2])
                     for e in hit]):
                print(f"      {t / 1e6:10.3f} ms  x{n:<6d} {name[:100]}")
    if args.dump:
        tr.dump_small(tr.read_xplane(path), args.dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
