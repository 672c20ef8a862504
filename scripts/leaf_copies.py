"""Does a device capture hold a copy of a state leaf?

    python scripts/leaf_copies.py <trace_dir> "f32[7,129,4096,256]"

Lists, by program, every device op of the capture whose result or operands
name that shape, with its calls and seconds, and exits 1 if one of them is a
`copy` (an in-place kernel's point is that there is none: PERF.md section 7,
ROADMAP M3).  The capture is a traced benchmark run's
(`.bench_out/<cell>/trace`).
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import trace_reduce  # noqa: E402


def main() -> int:
    trace_dir, shape = sys.argv[1], sys.argv[2]
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        print(f"leaf_copies: no capture under {trace_dir}", file=sys.stderr)
        return 2
    seen = collections.defaultdict(lambda: [0, 0.0])
    for plane in trace_reduce.load_xplane(path):
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            for name, _, dur in line["events"]:
                if shape in name:
                    op = re.sub(r"\s+", " ", name)[:160]
                    seen[op][0] += 1
                    seen[op][1] += dur / 1e9
    copies = 0
    for op, (calls, seconds) in sorted(seen.items(), key=lambda kv: -kv[1][1]):
        is_copy = bool(re.match(r"^%?copy[.\d]* = ", op))
        copies += is_copy
        print(json.dumps({"op": op, "calls": calls,
                          "seconds": round(seconds, 6), "copy": is_copy}))
    print(json.dumps({"shape": shape, "ops": len(seen), "copies": copies}))
    return 1 if copies else 0


if __name__ == "__main__":
    raise SystemExit(main())
