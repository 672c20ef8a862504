"""Admission, parking, batching: % of the window the engine thread lost
AFTER it asked to be woken (`/metrics` `sched.wait_over_s`, window delta over
the window's seconds on the server's clock): over the timed inbox waits that
ran out (1.5 ms under a decode hold, 1 s when idle), elapsed less the timeout
asked for, i.e. what the GIL and the OS kept from the thread.  None on a
program without the account."""
import sched_account


def read(ctx):
    d = sched_account.window(ctx)
    return None if d is None else sched_account.share(d["wait_over_s"], d)
