"""Look at one trace by hand: planes, lines, the first events with their
stats.  ``python3 benchmark/tools/dump_trace.py <dir or .xplane.pb> [n]``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    from jax.profiler import ProfileData

    from benchmark.harness import trace

    path = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for ev in events[:n]:
                stats = {k: (v if not isinstance(v, (bytes, str)) else str(v)[:60]) for k, v in ev.stats}
                print("     ", ev.name[:70], ev.start_ns, ev.duration_ns, stats)
    return 0


if __name__ == "__main__":
    sys.exit(main())
