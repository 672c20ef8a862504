"""Fetch pipeline: % of the window the scheduler thread sat in a read whose
transfer had not landed (`/metrics` `engine.fetch_blocked_s`, window delta
over the window's seconds; per replica under dp): time in which nothing is
admitted, dispatched or polled, and by which the engine's completion stamps
are late.  The window's pops by what released them
(`engine.fetch_pops`) are printed beside it.  None on a program without the
counter."""
import json
import sys

import readers


def read(ctx):
    blocked = readers.counter_delta(ctx, "engine", "fetch_blocked_s")
    seconds = ctx["t_close"] - ctx["t_open"]
    if blocked is None or seconds <= 0:
        return None
    print("fetch_stages: reads " + json.dumps({
        "blocked_s": blocked, "window_s": seconds,
        "pops": {k: v - ctx["before"]["engine"]["fetch_pops"][k] for k, v
                 in ctx["after"]["engine"]["fetch_pops"].items()}}),
        file=sys.stderr, flush=True)
    replicas = len(ctx["after"].get("replicas") or [None])
    return 100.0 * blocked / (seconds * replicas)
