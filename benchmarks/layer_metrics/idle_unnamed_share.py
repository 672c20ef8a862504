"""Device, on the profiler's clock: % of the worst chip's idle seconds in the
capture that NO phase of the engine thread covers.  Under profiling every
phase of the thread's clock is a host annotation `kafka.sched.<phase>`
(`kafka_tpu.tracing.SCHED_PHASES`), opened and closed by the call that feeds
the counter, and `sched_account.idle_by_phase` charges each idle gap to them
by overlap; 0.0 where the chip never idled.  Printed beside it: the idle
seconds by phase, and the program's own account of the same gaps over the
traced seconds (`sched_window.at_start` .. `at_stop_call` of the
/debug/profile reply, or of the window's last /metrics snapshot): `starved_<phase>_s` / `starved_hi_<phase>_s` and the two bounds
`dev_starved_s` <= idle <= `dev_starved_hi_s`, each also as a share of its
interval (the device lines begin a little after `at_start`).  None without a
capture; a capture of a program without the annotations (the parent) reads
None too, not 100: there is nothing there to be unnamed against."""
import json
import sys

import sched_account


def read(ctx):
    found = sched_account.capture_idle(ctx)
    if found is None or not found["phase_spans"]:
        return None
    report = dict(found, idle_share=(
        100.0 * found["idle_s"] / found["window_s"]
        if found["window_s"] else 0.0))
    brackets = sched_account.capture_brackets(ctx)
    if brackets is not None:
        t = brackets["traced"]
        report["program_over_traced_seconds"] = {
            "seconds": t["interval_s"] * t["threads"],
            "dev_starved_s": t["dev_starved_s"],
            "dev_starved_hi_s": t["dev_starved_hi_s"],
            "share": sched_account.share(t["dev_starved_s"], t),
            "share_hi": sched_account.share(t["dev_starved_hi_s"], t),
            **{"starved" + hi + "_by_phase_s": {
                k: round(v, 6) for k, v in sorted(
                    t["starved" + hi + "_by_phase"].items(),
                    key=lambda kv: -kv[1]) if v} for hi in ("", "_hi")}}
    print("sched_account: idle " + json.dumps(report),
          file=sys.stderr, flush=True)
    return found["unnamed_share"]
