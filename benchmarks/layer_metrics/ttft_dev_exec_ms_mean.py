"""Fetch pipeline: mean over the window of `ttft_dev_exec_ms`, the second
stage of the first-fetch phase: the device starts the last prefill chunk ->
its completion as the scheduler observed it (a poll's `is_ready`, or the
return of the read where the entry was popped unseen), so the device's run
of the chunk plus up to one poll interval.  None on a program without the
histogram."""
import fetch_stages


def read(ctx):
    return fetch_stages.hist_delta_mean(ctx, "ttft_dev_exec_ms")
