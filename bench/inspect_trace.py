"""Print what a profiler trace holds, to read it by hand.

    python bench/inspect_trace.py <trace dir> [--events N]

Lists every plane and line of the ``.xplane.pb`` with its event count,
the busiest event names of each line, and the first events with their
start and duration (ns), so that the names ``benchlib/trace.py`` looks
for can be checked against a real chip trace.
"""

from __future__ import annotations

import argparse
import collections
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--events", type=int, default=5)
    ap.add_argument("--save-events", default=None,
                    help="write the extracted events of the first --ms "
                         "milliseconds of the traced window as JSON")
    ap.add_argument("--ms", type=float, default=200.0)
    a = ap.parse_args(argv)
    from jax.profiler import ProfileData
    from benchlib.trace import find_xplane
    path = find_xplane(a.trace_dir)
    print(f"file {path}")
    if a.save_events:
        import json
        from benchlib.trace import extract, short_name
        ev = extract(path)
        ev["ops"] = [(short_name(n), s, d) for n, s, d in ev["ops"]]
        win = [e for e in ev["host"] if e[0] == "window"]
        lo = win[0][1] if win else 0
        hi = lo + int(a.ms * 1e6)
        keep = {k: [e for e in v if lo <= e[1] < hi] for k, v in ev.items()}
        keep["host"].append(("window", lo, hi - lo))
        keep["host"] = [e for e in keep["host"]
                        if e[0] != "window" or e[1:] == (lo, hi - lo)]
        with open(a.save_events, "w") as f:
            json.dump(keep, f)
        print(f"saved {sum(map(len, keep.values()))} events to "
              f"{a.save_events}")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines {len(lines)}")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            total = collections.Counter()
            count = collections.Counter()
            for e in evs:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r} events {len(evs)} span "
                  f"{lo}..{hi} ns")
            for n, d in total.most_common(8):
                print(f"    top {n!r}: {count[n]} events, {d} ns")
            for e in evs[:a.events]:
                print(f"    ev {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
