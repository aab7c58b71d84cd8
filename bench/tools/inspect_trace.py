#!/usr/bin/env python3
"""Print what a profiler trace holds: its planes, their lines, event
counts, the statistics events carry and a few sample events, so the
names the reduction relies on can be read by hand.

    python bench/tools/inspect_trace.py <file.xplane.pb>
"""
from __future__ import annotations

import collections
import sys


def main(path: str) -> int:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            keys = collections.Counter()
            for e in evs[:2000]:
                try:
                    keys.update(k for k, _ in e.stats)
                except Exception:
                    pass
            print(f"  LINE {line.name!r} events={len(evs)} "
                  f"stat_keys={dict(keys)}")
            for n, c in names.most_common(12):
                print(f"      {c:7d}  {n[:120]}")
            for e in evs[:3]:
                try:
                    st = dict(e.stats)
                except Exception:
                    st = {}
                print(f"      sample {e.name[:80]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={str(st)[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
