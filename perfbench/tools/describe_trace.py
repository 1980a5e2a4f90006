#!/usr/bin/env python3
"""Print what a profiler capture holds: planes, lanes, a few events of
each with their stats. Look at one capture by hand before changing
lib/trace_reduce.py.

    python3 perfbench/tools/describe_trace.py <dir or .xplane.pb> [n]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import trace_reduce  # noqa: E402


def _stats(ev) -> dict:
    out = {}
    for stat in getattr(ev, "stats", ()) or ():
        if isinstance(stat, (tuple, list)) and len(stat) == 2:
            out[str(stat[0])] = stat[1]
    return out


def main(path, n=3):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs), "events")
            for ev in evs[:n]:
                print("    ", ev.name, ev.start_ns, ev.duration_ns,
                      _stats(ev))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 3)
