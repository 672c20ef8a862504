"""HTTP + agent loop + provider: % of the window the engine thread spent
handing token events to the event loop (`/metrics` `sched.deliver_s`, window
delta over the window's seconds on the server's clock): one
`loop.call_soon_threadsafe` an event, each a write to the loop's wake-up
socket that lets go of the GIL to a loop serialising SSE.  The events
delivered and the seconds an event are printed beside it.  None on a program
without the account."""
import json
import sys

import sched_account


def read(ctx):
    d = sched_account.window(ctx)
    if d is None or "deliver" not in d["by_phase"]:
        return None
    seconds, n = d["by_phase"]["deliver"], d["delivered"]
    print("sched_account: deliver " + json.dumps({
        "deliver_s": seconds, "delivered": n,
        "us_an_event": 1e6 * seconds / n if n else None}),
        file=sys.stderr, flush=True)
    return sched_account.share(seconds, d)
