"""Device: % of the window the device had nothing queued while lanes were
active, as the HOST knows it (`/metrics` `sched.dev_starved_s`, the lower
bound: from the poll that saw the last queued program done to the start of
the next dispatch call), taken over the window LESS the capture's bracket:
`/debug/profile` replies with the account at `sched_window.at_start` and
`.at_stop_return`, which bracket the traced seconds and stop_trace's (GIL-
bound for seconds after a 5 s capture), so what is left is the window where
nobody was tracing.  Where stop_trace had not returned when the window
closed (the marks taken so far are then read from the window's last /metrics
snapshot), the window up to `at_start`; without any mark, or with a bracket
that does not lie inside the window, the whole window.  run.py reads per-layer metrics on the
traced run only: this is the nearest the ledger gets to an untraced idle
share.  Printed beside it: the whole window's value, the upper bound
(`dev_starved_hi_s`: from the last look that saw the program running to the
call's return), the bracket's own, and each bound by the phase the engine
thread was in (`starved_<phase>_s`, `starved_hi_<phase>_s`).  None on a program without the
account."""
import json
import sys

import sched_account


def by_phase(table):
    return {k: round(v, 6) for k, v in sorted(
        table.items(), key=lambda kv: -kv[1]) if v}


def read(ctx):
    whole = sched_account.window(ctx)
    if whole is None:
        return None
    lo, hi = whole["dev_starved_s"], whole["dev_starved_hi_s"]
    seconds = whole["interval_s"] * whole["threads"]
    report = {
        "whole_window": {
            "seconds": seconds, "dev_starved_s": lo, "dev_starved_hi_s": hi,
            "gaps": whole["dev_starved_gaps"],
            "share": sched_account.share(lo, whole),
            "share_hi": sched_account.share(hi, whole)},
        "starved_by_phase_s": by_phase(whole["starved_by_phase"]),
        "starved_hi_by_phase_s": by_phase(whole["starved_hi_by_phase"]),
    }
    brackets = sched_account.capture_brackets(ctx) or {}
    out = (lo, seconds)
    b = brackets.get("disturbed")
    if b is not None:
        inside = ctx["wall_open"] <= b["t0"] and b["t1"] <= ctx["wall_close"]
        report["capture_bracket"] = {
            "inside_window": inside, "wall_s": b["t1"] - b["t0"],
            "seconds": b["interval_s"] * b["threads"],
            "dev_starved_s": b["dev_starved_s"],
            "dev_starved_hi_s": b["dev_starved_hi_s"],
            "share": sched_account.share(b["dev_starved_s"], b)}
        left = seconds - b["interval_s"] * b["threads"]
        if inside and left > 0:
            out = (lo - b["dev_starved_s"], left)
            report["less_bracket"] = {
                "seconds": left, "dev_starved_s": out[0],
                "dev_starved_hi_s": hi - b["dev_starved_hi_s"]}
    elif "before" in brackets and brackets["before"]["interval_s"] > 0:
        # stop_trace had not returned when the window closed: what came
        # before the capture is all of the window that nobody disturbed
        b = brackets["before"]
        out = (b["dev_starved_s"], b["interval_s"] * b["threads"])
        report["before_capture"] = {
            "seconds": out[1], "dev_starved_s": out[0],
            "dev_starved_hi_s": b["dev_starved_hi_s"],
            "starved_hi_by_phase_s": by_phase(b["starved_hi_by_phase"])}
    print("sched_account: starved " + json.dumps(report),
          file=sys.stderr, flush=True)
    return 100.0 * max(out[0], 0.0) / out[1] if out[1] > 0 else 0.0
