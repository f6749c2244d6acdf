"""Print the structure of a profiler trace (`*.xplane.pb`): planes, their
lines, and for each line the event names that took most time with their
stats. Look at one trace by hand with this before writing a reduction
against it (benchmark/lib/trace_reduce.py).

    python benchmark/tools/dump_trace.py <trace dir or .xplane.pb> [out.json]
"""
from __future__ import annotations

import collections
import glob
import json
import os
import sys


def newest_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def structure(path: str, top: int = 25) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(newest_xplane(path))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            total = collections.defaultdict(float)
            count = collections.Counter()
            sample = {}
            first, last = None, None
            for ev in line.events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                if ev.name not in sample:
                    sample[ev.name] = {k: str(v)[:200]
                                       for k, v in list(ev.stats)[:12]}
                first = ev.start_ns if first is None else min(first,
                                                              ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                last = end if last is None else max(last, end)
            names = sorted(total, key=total.get, reverse=True)[:top]
            lines[line.name] = {
                "events": sum(count.values()), "distinct": len(total),
                "first_ns": first, "last_ns": last,
                "top": [{"name": n, "seconds": total[n] / 1e9,
                         "count": count[n], "stats": sample[n]}
                        for n in names]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    report = structure(sys.argv[1])
    text = json.dumps(report, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as fh:
            fh.write(text)
    else:
        print(text)
