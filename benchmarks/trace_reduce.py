"""Reduce a jax.profiler xplane capture to what the per-layer readers need.

    python benchmarks/trace_reduce.py --dump <file.xplane.pb>   # look at one
    python benchmarks/trace_reduce.py <trace_dir>               # reduce, print

The arithmetic (`reduce_planes`) works on plain lists, so it is tested without
a profile; `load_xplane` turns `jax.profiler.ProfileData` into those lists and
is checked against the recorded v5e trace in `benchmarks/tests/recorded/`.

What a v5e capture holds (PERF.md section 6, PR 22): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per program
launch, named `<jit name>(<fingerprint>)`) and `XLA Ops` (one event per HLO
op, named by its whole HLO text, nested: a `while` covers its body's ops),
and a `/host:CPU` plane whose thread lines carry the program's
`jax.profiler.TraceAnnotation` spans and, from the profiler's Python tracer,
one span per Python call (`$engine.py:2513 _drain`).

Device busy time is the union of the `XLA Ops` intervals (not their sum:
ops nest).  An op's own time, for the table of device ops, is its duration
minus what its children on the same line cover.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_FN = re.compile(r"^\$engine\.py:\d+ (\w+)")
TOP = 10
LOOK_BACK = 512  # spans before a gap that may still cover it (call depth)


def union_ns(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total covered length and the merged intervals of (start, end) pairs."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def self_times(events: List[Event]) -> Dict[str, int]:
    """Per op name, duration minus the time nested ops on the line cover."""
    out: Dict[str, int] = {}
    stack: List[List[Any]] = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + max(own, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


def leaf_loops(events: List[Event]) -> List[int]:
    """Start times of the `while` ops that hold no other `while`: in a
    program that scans over layers (and, fused, over steps outside that) one
    innermost loop runs per forward pass."""
    loops = sorted(((s, s + d) for n, s, d in events
                    if base_name(n) == "while"), key=lambda e: (e[0], -e[1]))
    out: List[int] = []
    for i, (s, e) in enumerate(loops):
        nxt = loops[i + 1] if i + 1 < len(loops) else None
        if nxt is None or nxt[0] >= e:
            out.append(s)
    return out


INSTR = re.compile(r"^%?([A-Za-z_][\w.\-]*)")
SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")


def instr_name(name: str) -> str:
    """The device lines print an op as its whole HLO text
    (`%fusion.248 = bf16[16,11008]{...} fusion(...)`) and a program as
    `jit_fn(<fingerprint>)`: keep the instruction's name."""
    m = INSTR.match(name)
    return m.group(1) if m else name


def base_name(name: str) -> str:
    """`%fusion.123 = ...` -> `fusion`; a Pallas kernel keeps the name of the
    jitted function that wraps it (`paged_decode_attention`)."""
    return re.sub(r"[.\-_]\d+$", "", instr_name(name))


def op_label(name: str) -> str:
    """Short label for the table of device ops: instruction and result shape."""
    m = SHAPE.search(name)
    return instr_name(name) + (" " + m.group(1) if m else "")


def is_kernel(name: str) -> bool:
    return "tpu_custom_call" in name


def host_label(name: str) -> Optional[str]:
    """What the host was doing, from the spans the capture holds: the
    program's TraceAnnotation (`kafka.*`) or, from the profiler's Python
    tracer, a function of runtime/engine.py (`$engine.py:2513 _drain`)."""
    if name.startswith("kafka."):
        return re.sub(r"\[.*\]$", "", name)
    m = HOST_FN.match(name)
    return f"engine.py {m.group(1)}" if m else None


def reduce_planes(planes: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """planes: [{"name", "lines": [{"name", "events": [Event]}]}]."""
    devices = []
    annotations: List[Tuple[str, int, int]] = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            devices.append((plane["name"], lines.get(OPS_LINE, []),
                            lines.get(MODULES_LINE, [])))
        else:
            for ln in plane["lines"]:
                annotations += [(host_label(n), s, s + d)
                                for n, s, d in ln["events"] if host_label(n)]
    devices = [d for d in devices if d[1]]
    if not devices:
        return None
    # the traced window is what the device lines cover: device tracing starts
    # a few hundred ms after host tracing, which is not idle time
    t0 = min(e[1] for _, ops, _ in devices for e in ops)
    t1 = max(e[1] + e[2] for _, ops, _ in devices for e in ops)
    window_ns = max(t1 - t0, 1)
    per_device, op_self, op_base, op_count, modules = [], {}, {}, {}, {}
    gaps: List[Tuple[int, int, str]] = []
    for name, ops, mods in sorted(devices):
        busy, merged = union_ns([(s, s + d) for _, s, d in ops])
        per_device.append({"plane": name, "busy_s": busy / 1e9,
                           "idle_share": 1.0 - busy / window_ns})
        for k, v in self_times(ops).items():
            op_self[op_label(k)] = op_self.get(op_label(k), 0) + v
            op_base[base_name(k)] = op_base.get(base_name(k), 0) + v
        for oname, _, _ in ops:
            b = base_name(oname)
            op_count[b] = op_count.get(b, 0) + 1
        mod_sorted = sorted(mods, key=lambda e: e[1])
        for mname, s, d in mod_sorted:
            m = modules.setdefault(mname, {"count": 0, "total_s": 0.0,
                                           "loops": 0, "kernels": {}})
            m["count"] += 1
            m["total_s"] += d / 1e9
        # which custom kernels run inside which program
        if mod_sorted:
            starts = [m[1] for m in mod_sorted]
            for oname, s, d in ops:
                if not is_kernel(oname):
                    continue
                b = base_name(oname)
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mod_sorted[i][1] + mod_sorted[i][2]:
                    k = modules[mod_sorted[i][0]]["kernels"]
                    k[b] = k.get(b, 0.0) + d / 1e9
            # innermost loops: one per decode step (the scan over layers)
            for s in leaf_loops(ops):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mod_sorted[i][1] + mod_sorted[i][2]:
                    modules[mod_sorted[i][0]]["loops"] += 1
        edges = [(t0, t0)] + merged + [(t1, t1)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0, s1, name))
    worst = max(per_device, key=lambda d: d["idle_share"])
    idle: Dict[str, float] = {}
    ann_sorted = sorted(annotations, key=lambda a: a[1])
    ann_starts = [a[1] for a in ann_sorted]
    for g0, g1, plane in gaps:
        if plane != worst["plane"]:
            continue
        mid = (g0 + g1) // 2
        label, span = "host outside engine.py and kafka.* spans", 1 << 62
        i = bisect.bisect_right(ann_starts, mid)
        for n, s, e in ann_sorted[max(0, i - LOOK_BACK):i]:
            if mid < e and e - s < span:  # the innermost span wins
                label, span = "host in " + n, e - s
        idle[label] = idle.get(label, 0.0) + (g1 - g0) / 1e9
    longest = sorted(((g1 - g0) / 1e9 for g0, g1, p in gaps
                      if p == worst["plane"]), reverse=True)[:3]
    top_ops = sorted(op_self.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "devices": per_device,
        "worst_idle_share": worst["idle_share"],
        "op_self_s": {k: v / 1e9 for k, v in sorted(
            op_base.items(), key=lambda kv: -kv[1])[:200]},
        "op_count": op_count,
        "modules": modules,
        "longest_gaps_s": longest,
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops[:TOP]],
            "idle_gaps": [[k, v] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def load_xplane(path: str) -> List[Dict[str, Any]]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        host = not DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if not host and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events
                      if not host or host_label(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    path = find_xplane(trace_dir)
    return reduce_planes(load_xplane(path)) if path else None


def dump(path: str, top: int = 25) -> None:
    """Print what a capture holds: planes, lines, the heaviest event names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            tot: Dict[str, List[float]] = {}
            n = 0
            first = None
            for ev in line.events:
                n += 1
                first = first or ev
                t = tot.setdefault(ev.name, [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns / 1e6
            if n == 0:
                continue
            print(f"  line {line.name!r}: {n} events, {len(tot)} names")
            if first is not None:
                try:
                    stats = {k: str(v)[:80] for k, v in first.stats}
                except Exception as e:  # stats are optional in a dump
                    stats = {"error": repr(e)}
                print(f"    first: {first.name!r} start_ns={first.start_ns} "
                      f"dur_ns={first.duration_ns} stats={stats}")
            for name, (c, ms) in sorted(
                    tot.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"    {ms:12.3f} ms  x{c:<7d} {name[:110]}")


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if len(sys.argv) >= 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        import json

        red = reduce_dir(sys.argv[1])
        print(json.dumps(red, indent=1)[:20000])
