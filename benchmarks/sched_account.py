"""What the scheduler-account readers share (PR 52): window deltas of the
engine thread's account, and the capture's idle gaps charged to its phases.

Program side.  `/metrics` `sched` (`kafka_tpu.runtime.phase_clock.SchedClock`)
holds `<phase>_s` for every phase of `kafka_tpu.tracing.SCHED_PHASES`: every
instant of the engine thread belongs to one phase, so over any interval the
deltas add up to the interval's seconds on the server's clock (times
`threads`, where each replica has a thread of its own).  The shares below
take that sum as their denominator, not the client's clock: the two
snapshots are HTTP requests and answer a little after they are asked.
`dev_starved_s` / `dev_starved_hi_s` bound from below and above the seconds
the device had nothing queued while lanes were active, and
`starved_<phase>_s` / `starved_hi_<phase>_s` charge each bound's interval to
what the thread did meanwhile.
A program without the section (the parent) has nothing to read: None.

Capture side.  Under profiling every phase is a host annotation
`kafka.sched.<phase>`, opened and closed by the same call that feeds the
counter.  `charge_gaps` lays the worst chip's idle gaps over them BY OVERLAP
(`trace_reduce.reduce_planes` labels a gap by the innermost span at its
midpoint): what no phase covers is `idle_unnamed_share`.

    python benchmarks/sched_account.py <trace_dir>    # idle by phase, printed
"""

from __future__ import annotations

import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

import trace_reduce

PHASE = re.compile(r"^kafka\.sched\.(\w+)$")
OTHER = re.compile(r"^(kafka\.metrics\.snapshot)$")
NOT_BUSY = ("idle_wait", "hold_wait", "paused")

Span = Tuple[str, int, int]  # label, start_ns, end_ns


# --------------------------------------------------------------------------
# program side
# --------------------------------------------------------------------------

def phases_of(section: Dict[str, Any]) -> Dict[str, float]:
    """`<phase>_s` of a `sched` section, by phase (the keys that have a
    `starved_<phase>_s` beside them: the bounds and `wait_over_s` have
    none)."""
    return {k[:-2]: float(v) for k, v in section.items()
            if k.endswith("_s") and "starved_" + k in section}


def delta(a: Dict[str, Any], b: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """What the account gained from section `b` to section `a`: seconds by
    phase, their sum over the threads (`interval_s`: the seconds between the
    two reads on the server's clock), and every other counter's gain."""
    try:
        pa, pb = phases_of(a), phases_of(b)
        if not pa or set(pa) != set(pb):
            return None
        by_phase = {p: pa[p] - pb[p] for p in pa}
        threads = max(int(a.get("threads", 1)), 1)
        out: Dict[str, Any] = {
            "by_phase": by_phase, "threads": threads,
            "interval_s": sum(by_phase.values()) / threads,
            "starved_by_phase": {p: float(a["starved_" + p + "_s"])
                                 - float(b["starved_" + p + "_s"])
                                 for p in pa},
            # the upper bound's interval by phase (it also holds the phase
            # in which the completion went unseen)
            "starved_hi_by_phase": {
                p: float(a["starved_hi_" + p + "_s"])
                - float(b["starved_hi_" + p + "_s"])
                for p in pa if "starved_hi_" + p + "_s" in a},
        }
        for key in ("wait_over_s", "delivered", "dev_starved_s",
                    "dev_starved_hi_s", "dev_starved_gaps"):
            out[key] = a[key] - b[key]
        return out
    except (KeyError, TypeError, ValueError):
        return None


def window(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`delta` of the window's two snapshots; None where the program keeps
    no account."""
    try:
        return delta(ctx["after"]["sched"], ctx["before"]["sched"])
    except (KeyError, TypeError):
        return None


def share(part: float, d: Dict[str, Any]) -> float:
    """`part` seconds as % of the interval, a thread's."""
    whole = d["interval_s"] * d["threads"]
    return 100.0 * part / whole if whole > 0 else 0.0


def capture_brackets(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The account's gains around the capture, from the `sched_window` of
    the /debug/profile reply or, where the reply came after the window
    closed (stop_trace can take longer than the rest of it), of the
    window's last /metrics snapshot, which carries the marks taken so far:
    `traced` (at_start .. at_stop_call, the traced seconds), `disturbed`
    (at_start .. at_stop_return, the traced seconds and stop_trace's; absent
    while stop_trace runs) and `before` (the window's opening .. at_start),
    each with the wall times of its ends.  None without the marks."""
    sw = ((ctx.get("profile") or {}).get("sched_window")
          or (ctx.get("after") or {}).get("sched_window"))
    if not sw:
        return None
    try:
        start = sw["at_start"]
        out = {}
        for name, end in (("traced", "at_stop_call"),
                          ("disturbed", "at_stop_return")):
            if end in sw:
                d = delta(sw[end]["sched"], start["sched"])
                if d is not None:
                    out[name] = dict(d, t0=start["t"], t1=sw[end]["t"])
        d = delta(start["sched"], ctx["before"]["sched"])
        if d is not None:
            out["before"] = dict(d, t0=ctx["wall_open"], t1=start["t"])
        return out if "traced" in out else None
    except (KeyError, TypeError):
        return None


# --------------------------------------------------------------------------
# capture side
# --------------------------------------------------------------------------

def charge_gaps(gaps: List[Tuple[int, int]], spans: List[Span]
                ) -> Dict[str, int]:
    """ns of `gaps` (disjoint (start, end) pairs) that each label's spans
    cover, by overlap.  Spans of one label may touch, never overlap (one
    thread opens a phase as it closes the last), so a label's share of a
    gap is the sum of its intersections with it."""
    out: Dict[str, int] = {}
    gaps = sorted(gaps)
    by_label: Dict[str, List[Tuple[int, int]]] = {}
    for label, s, e in spans:
        by_label.setdefault(label, []).append((s, e))
    for label, ivs in by_label.items():
        ivs.sort()
        total, i = 0, 0
        for g0, g1 in gaps:
            while i < len(ivs) and ivs[i][1] <= g0:
                i += 1
            j = i
            while j < len(ivs) and ivs[j][0] < g1:
                total += min(g1, ivs[j][1]) - max(g0, ivs[j][0])
                j += 1
        out[label] = total
    return out


def worst_chip_gaps(planes: List[Dict[str, Any]]
                    ) -> Optional[Dict[str, Any]]:
    """The idle gaps of the chip that idles most, as
    `trace_reduce.reduce_planes` finds them: the traced window is what the
    device lines of all chips cover, a chip's busy time the union of its
    `XLA Ops` intervals, a gap what lies between."""
    chips = []
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [ev for ln in plane["lines"]
               if ln["name"] == trace_reduce.OPS_LINE for ev in ln["events"]]
        if ops:
            chips.append((plane["name"], ops))
    if not chips:
        return None
    t0 = min(s for _, ops in chips for _, s, _ in ops)
    t1 = max(s + d for _, ops in chips for _, s, d in ops)
    worst = None
    for name, ops in sorted(chips):
        busy, merged = trace_reduce.union_ns([(s, s + d) for _, s, d in ops])
        edges = [(t0, t0)] + merged + [(t1, t1)]
        gaps = [(e0, s1) for (_, e0), (s1, _) in zip(edges, edges[1:])
                if s1 > e0]
        if worst is None or busy < worst["busy_ns"]:
            worst = {"plane": name, "busy_ns": busy, "gaps": gaps}
    return dict(worst, t0=t0, t1=t1)


def host_spans(planes: List[Dict[str, Any]]) -> List[Span]:
    """The capture's `kafka.sched.<phase>` annotations, labelled by phase,
    and the spans of what else this module knows by name (a /metrics
    snapshot being built, on another thread)."""
    out: List[Span] = []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                m = PHASE.match(name) or OTHER.match(name)
                if m:
                    out.append((m.group(1), s, s + d))
    return out


def idle_by_phase(planes: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Seconds of the worst chip's idle gaps by the engine thread's phase,
    the seconds no phase covers, and that as % of the idle seconds (0.0
    where the chip never idled).  None without device lines."""
    found = worst_chip_gaps(planes)
    if found is None:
        return None
    spans = host_spans(planes)
    phases = [sp for sp in spans if not OTHER.match(sp[0])]
    idle_ns = sum(e - s for s, e in found["gaps"])
    covered = charge_gaps(found["gaps"], phases)
    unnamed = max(idle_ns - sum(covered.values()), 0)
    beside = charge_gaps(found["gaps"],
                         [sp for sp in spans if OTHER.match(sp[0])])
    return {
        "plane": found["plane"],
        "window_s": (found["t1"] - found["t0"]) / 1e9,
        "idle_s": idle_ns / 1e9,
        "gaps": len(found["gaps"]),
        "by_phase_s": {k: v / 1e9 for k, v in sorted(
            covered.items(), key=lambda kv: -kv[1])},
        "unnamed_s": unnamed / 1e9,
        "unnamed_share": 100.0 * unnamed / idle_ns if idle_ns else 0.0,
        # on other threads, so not part of the tiling: how much of the idle
        # time ran beside them
        "beside_s": {k: v / 1e9 for k, v in beside.items()},
        "phase_spans": len(phases),
    }


def capture_idle(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`idle_by_phase` of the cell's capture, read once from where run.py
    had it put; None without a capture."""
    if "idle_by_phase" not in ctx:
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".bench_out", ctx["cell"].name, "trace")
        ) if ctx.get("trace") else None
        ctx["idle_by_phase"] = (
            None if path is None
            else idle_by_phase(trace_reduce.load_xplane(path)))
    return ctx["idle_by_phase"]


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json

    found = trace_reduce.find_xplane(sys.argv[1])
    print(json.dumps(
        found and idle_by_phase(trace_reduce.load_xplane(found)), indent=1))
