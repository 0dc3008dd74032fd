#!/usr/bin/env python3
"""Print the shape of a profiler trace: planes, lines, event counts and
the most frequent event names with their stats, to look at before
writing a reader against it.

    python bench/tools/trace_dump.py <trace dir> [events per line]
"""
from __future__ import annotations

import collections
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(argv):
    import jax

    from bench.trace_reduce import find_xplane
    path = find_xplane(argv[0])
    n = int(argv[1]) if len(argv) > 1 else 12
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{t0:.0f}..{t1:.0f} ns")
            by = collections.Counter(e.name for e in evs)
            dur = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            for name, c in by.most_common(n):
                ex = next(e for e in evs if e.name == name)
                stats = [(k, str(v)[:60]) for k, v in list(ex.stats)[:8]]
                print(f"    {c:7d} x {name[:90]!r} {dur[name] / 1e9:.4f}s "
                      f"{stats}")


if __name__ == "__main__":
    main(sys.argv[1:])
