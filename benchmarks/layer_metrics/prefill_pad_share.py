"""Jitted step programs: of the rows the prefill programs dispatched over the
window (lanes x bucket a launch), the % that held no token: what the static
buckets pad.  The engine counts both (`/metrics`
`engine.prefill_rows_dispatched`, `engine.prefill_rows_filled`, monotonic;
under dp the aggregate's `engine` group sums the replicas'); the window's
share is 1 - delta filled / delta dispatched.  On the Pallas path it is the
share of q rows the flash-prefill kernel skips, on the XLA path the share it
computes for nothing.  A program without the counters (the parent) or a
window without a prefill launch has nothing to read: None."""
import readers


def read(ctx):
    rows = readers.counter_delta(ctx, "engine", "prefill_rows_dispatched")
    filled = readers.counter_delta(ctx, "engine", "prefill_rows_filled")
    if not rows or filled is None:
        return None
    return 100.0 * (1.0 - filled / rows)
