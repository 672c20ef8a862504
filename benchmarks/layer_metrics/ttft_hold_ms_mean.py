"""Fetch pipeline: mean over the window of `ttft_hold_ms`, the third stage
of the first-fetch phase: the chunk's completion observed -> its entry
popped from the fetch FIFO, i.e. what the pop rules cost (FIFO order behind
older entries, the age and landed bounds, a scheduler thread blocked in
another entry's read).  The remainder of the phase, `ttft_emit_ms` (pop ->
first token on the host), has a histogram and no metric.  None on a program
without the histogram."""
import fetch_stages


def read(ctx):
    return fetch_stages.hist_delta_mean(ctx, "ttft_hold_ms")
