"""Admission, parking, batching: % of the window the engine thread was NOT
waiting: the window delta of every phase of `/metrics` `sched` but
`idle_wait`, `hold_wait` and `paused`, over the delta of all of them (the
window's seconds on the server's clock; a thread's under dp).  100 is a host
that never waits: the device then runs only as fast as the thread steps.
The table by phase is printed beside it, with the sum of the phases laid
against the server's `uptime_s` delta and the client's window (they agree to
the snapshots' own latency: the phases tile the thread's time).  None on a
program without the account."""
import json
import sys

import sched_account


def read(ctx):
    d = sched_account.window(ctx)
    if d is None:
        return None
    waiting = sum(d["by_phase"].get(p, 0.0) for p in sched_account.NOT_BUSY)
    whole = d["interval_s"] * d["threads"]
    try:
        uptime = ctx["after"]["uptime_s"] - ctx["before"]["uptime_s"]
    except (KeyError, TypeError):
        uptime = None
    print("sched_account: window " + json.dumps({
        "by_phase_s": {k: round(v, 6) for k, v in sorted(
            d["by_phase"].items(), key=lambda kv: -kv[1])},
        "sum_s": whole, "threads": d["threads"],
        "uptime_delta_s": uptime,
        "client_window_s": ctx["t_close"] - ctx["t_open"],
        "sum_vs_uptime_pct": (None if not uptime else
                              100.0 * (d["interval_s"] - uptime) / uptime),
    }), file=sys.stderr, flush=True)
    return sched_account.share(whole - waiting, d)
