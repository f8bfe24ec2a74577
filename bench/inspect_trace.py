"""Print what a recorded trace holds, to read it by hand before writing
a reducer against it.

    python bench/inspect_trace.py <trace dir or .xplane.pb> [--events 5]

For every plane and line: the event count, the names that take most
time, and a few events with their statistics.
"""
from __future__ import annotations

import argparse
import collections
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--events", type=int, default=5)
    args = ap.parse_args(argv)
    import jax

    path = Path(args.path)
    if path.is_dir():
        path = sorted(path.glob("**/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            by_name = collections.Counter()
            for ev in events:
                by_name[ev.name] += ev.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events")
            for name, ns in by_name.most_common(8):
                print(f"    {ns / 1e6:12.3f} ms  {name}")
            for ev in events[:args.events]:
                stats = {k: v for k, v in ev.stats}
                print(f"    EVENT {ev.name} start {ev.start_ns} "
                      f"dur {ev.duration_ns} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
