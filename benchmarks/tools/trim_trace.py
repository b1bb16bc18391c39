"""Look at a raw trace by hand and cut the small recorded trace the tests
keep.

    BENCH_KEEP_TRACE=chiprun_out/trace python3 benchmarks/run.py --workload <cell> --trace 1 --seconds 20
    python3 benchmarks/tools/trim_trace.py chiprun_out/trace chiprun_out/events.json [seconds]

Prints every plane and line of the trace with its event count and first
names, then writes `load_events`' output cut to the first `seconds` (1.0)
after the first device operation, and removes the raw file (it is large).
"""

from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import trace_reduce  # noqa: E402


def main(argv) -> int:
    import jax

    src, dst = argv[0], argv[1]
    seconds = float(argv[2]) if len(argv) > 2 else 1.0
    path = sorted(glob.glob(os.path.join(src, "*.xplane.pb")))[-1]
    print("raw trace", path, os.path.getsize(path), "bytes")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            names, n = [], 0
            for e in line.events:
                n += 1
                if len(names) < 12 and e.name not in names:
                    names.append(e.name)
            print("  LINE", line.name, n, names)
    events = trace_reduce.load_events(path)
    starts = [e[1] for d in events["devices"].values() for e in d["ops"]]
    if starts:
        t0 = min(starts)
        t1 = t0 + seconds * 1e9
        for dev in events["devices"].values():
            for key in ("modules", "ops"):
                dev[key] = [e for e in dev[key] if t0 <= e[1] < t1]
    with open(dst, "w") as f:
        json.dump(events, f)
    print("kept", {k: {kk: len(vv) for kk, vv in d.items()}
                   for k, d in events["devices"].items()}, "->", dst,
          os.path.getsize(dst), "bytes")
    os.remove(path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
