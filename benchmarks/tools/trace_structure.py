#!/usr/bin/env python3
"""Print what a ``.xplane.pb`` holds: planes, lines, event counts, the
first events of each line with their stats, and the heaviest op groups of
each device. For looking at a trace by hand before trusting a reduction.

    python3 benchmarks/tools/trace_structure.py <file.xplane.pb> [top]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import xplane  # noqa: E402


def main(path, top=25):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE %r" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE %r: %d events" % (line.name, len(events)))
            for ev in events[:3]:
                print("    %r start %.0f ns dur %.0f ns stats %r" % (
                    ev.name, ev.start_ns, ev.duration_ns,
                    [(k, str(v)[:60]) for k, v in list(ev.stats)[:8]]))
    planes = xplane.load(path)
    for dev, events in sorted(xplane.device_ops(planes).items()):
        print("DEVICE %d: busy %.6f s over %d events" % (
            dev, xplane.busy_seconds(events), len(events)))
        groups = sorted(xplane.per_op_seconds(events).items(),
                        key=lambda kv: -kv[1][0])
        for name, (secs, n) in groups[:top]:
            print("    %-60s %.6f s x%d" % (name, secs, n))
    print("ANNOTATIONS:", xplane.annotations(planes)[:12])


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
