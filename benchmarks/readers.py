"""What the per-layer readers share: window deltas of the server's counters
and histograms, and look-ups in the reduced trace.  A reader is
`layer_metrics/<name>.py` with `read(ctx) -> value | None`; `ctx` holds

    before, after   /metrics snapshots at the window's opening and closing
    log             the client log (loadgen.one_request records)
    summary         e2e.summarize of the window
    trace           trace_reduce.reduce_planes of the capture, or None
    profile         the /debug/profile reply (flight_window.t_start/t_end)
    info, health    /bench/info and /health after the window
    cell            run.Cell: config (the configuration file), params, chips
    wall_open, t_open, wall_close, t_close   the window on both clocks
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional


def hist_delta_quantile(ctx: Dict[str, Any], name: str,
                        q: float) -> Optional[float]:
    """Quantile of a server histogram over the window only: per-bucket
    counts after minus before, log-linear inside the bucket."""
    try:
        a = ctx["after"]["histograms"][name]
        b = ctx["before"]["histograms"][name]
    except (KeyError, TypeError):
        return None
    counts = [x - y for x, y in zip(a["counts"], b["counts"])]
    total = sum(counts)
    if total <= 0:
        return None
    bounds = list(a["le"])
    target, acc = q * total, 0.0
    for i, c in enumerate(counts):
        if c > 0 and acc + c >= target:
            hi = bounds[i] if i < len(bounds) else a.get("max", bounds[-1])
            lo = bounds[i - 1] if i > 0 else hi / 2.0
            frac = (target - acc) / c
            return lo * (hi / lo) ** frac if lo > 0 else hi * frac
        acc += c
    return None


def counter_delta(ctx: Dict[str, Any], *path: str) -> Optional[float]:
    def get(snap):
        for p in path:
            snap = snap[p]
        return snap

    try:
        return get(ctx["after"]) - get(ctx["before"])
    except (KeyError, TypeError):
        return None


START_TRACE_S = 0.5  # what start_trace takes before the device lines begin


def capture_wall(ctx: Dict[str, Any]) -> Optional[tuple]:
    """The traced interval on the wall clock, estimated: the reply's
    flight_window.t_start..t_end also brackets start_trace and stop_trace
    (8.8 s for a 5 s capture), so the interval is taken to begin
    START_TRACE_S after t_start and to last as long as the device lines."""
    fw = (ctx.get("profile") or {}).get("flight_window") or {}
    trace = ctx.get("trace")
    if "t_start" not in fw or not trace:
        return None
    t0 = fw["t_start"] + START_TRACE_S
    return t0, t0 + trace["window_s"]


def to_client_clock(ctx: Dict[str, Any], wall: float) -> float:
    return ctx["t_open"] + (wall - ctx["wall_open"])


def op_seconds(ctx: Dict[str, Any], pattern: str) -> Optional[float]:
    """Self time of device ops whose name matches, summed over the chips."""
    trace = ctx.get("trace")
    if not trace:
        return None
    rx = re.compile(pattern)
    hits = [v for k, v in trace["op_self_s"].items() if rx.search(k)]
    return sum(hits) if hits else None


def op_calls(ctx: Dict[str, Any], pattern: str) -> Optional[int]:
    """How many times device ops whose name matches ran, over the chips."""
    trace = ctx.get("trace")
    if not trace:
        return None
    rx = re.compile(pattern)
    return sum(v for k, v in trace["op_count"].items() if rx.search(k)) or None


def batch_occupancy(ctx: Dict[str, Any]) -> Optional[float]:
    """Busy lanes per decode step over the window.  /metrics gives the running
    ratio busy_slots / steps; the window's is the ratio of the deltas.  Under
    dp the aggregate has no `decode` group: busy lanes and steps are summed
    over the replicas, so the value is still lanes per step of one replica."""
    after = ctx["after"].get("replicas") or [ctx["after"]]
    before = ctx["before"].get("replicas") or [ctx["before"]]
    if len(after) != len(before):
        return None
    steps = busy = 0.0
    for x, y in zip(after, before):
        a, b = x["decode"], y["decode"]
        steps += a["steps"] - b["steps"]
        busy += (a["batch_occupancy"] * a["steps"]
                 - b["batch_occupancy"] * b["steps"])
    return busy / steps if steps > 0 else None


def memory_peak_bytes(info: Dict[str, Any]) -> int:
    """`peak_bytes_in_use` of the fullest chip, from /bench/info."""
    return max((int(m.get("peak_bytes_in_use", 0))
                for m in info.get("memory_stats", [])), default=0)


def attention_shape(ctx: Dict[str, Any]) -> Dict[str, int]:
    """Heads and page size of the cell's configuration, as roofline.py's
    functions name them."""
    hf = ctx["cell"].config
    return {"num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf.get("head_dim",
                               hf["hidden_size"] // hf["num_attention_heads"]),
            "layers": hf["num_hidden_layers"],
            "page_size": hf["serving"]["page_size"]}
