#!/usr/bin/env python3
"""Print what a `.xplane.pb` holds: planes, lines, the first events of
each line with their statistics, and every distinct custom-call. For
looking at a trace by hand before trusting the reduction."""
import sys
from collections import Counter

from jax.profiler import ProfileData


def main(path, n=4):
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:n]:
                print(f"     {e.name!r} start={e.start_ns:.0f} dur={e.duration_ns:.0f} "
                      f"stats={dict(e.stats)}")
            if line.name == "XLA Ops":
                seen = Counter()
                for e in evs:
                    if e.name.startswith("custom-call") and seen[e.name] == 0:
                        print(f"     CUSTOM {e.name!r} dur={e.duration_ns:.0f} "
                              f"stats={dict(e.stats)}")
                    seen[e.name] += 1
                print("     most frequent:", seen.most_common(8))


if __name__ == "__main__":
    main(sys.argv[1])
