"""Fetch pipeline: the host's run-ahead in decode steps, mean over the
window's decode / fused / verify dispatches: steps already dispatched into
the fetch FIFO that the device had not been seen to finish when the next was
enqueued (`/metrics` `engine.fetch_depth_steps_sum` /
`engine.fetch_depth_samples`, window deltas; under dp summed over the
replicas, so still a mean per dispatch).  It is the number `fetch_lag`
bounds, and times the step's device time it is what a new prefill waits
behind.  None on a program without the counters."""
import readers


def read(ctx):
    steps = readers.counter_delta(ctx, "engine", "fetch_depth_steps_sum")
    samples = readers.counter_delta(ctx, "engine", "fetch_depth_samples")
    if steps is None or not samples:
        return None
    return steps / samples
