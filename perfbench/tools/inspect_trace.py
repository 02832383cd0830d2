"""Print what a recorded .xplane.pb holds: planes, lines, the commonest
event names.  For looking at a trace by hand before trusting the
reduction in harness/trace.py."""

import collections
import sys

from jax.profiler import ProfileData


def main(path):
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            dur = sum(e.duration_ns for e in events) * 1e-9
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{dur:.4f} s; top {names.most_common(6)}")


if __name__ == "__main__":
    main(sys.argv[1])
