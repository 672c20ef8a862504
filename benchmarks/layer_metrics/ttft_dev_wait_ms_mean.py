"""Fetch pipeline: mean over the window of `ttft_dev_wait_ms`, the first of
the four stages that tile the first-fetch phase of TTFT: last prefill chunk
dispatched -> the device starts it, i.e. the decode and prefill work the host
had already queued ahead (the engine's own stamps: `_Fetch.t_start`).  The
mean, because the four stages' means add up to the mean of `ttft_fetch_ms`
(`fetch_stages.tile`, printed here once a run) and a median of ~35 samples
scatters over the histogram's buckets.  None on a program without the
histogram."""
import json
import sys

import fetch_stages


def read(ctx):
    print("fetch_stages: tile " + json.dumps(fetch_stages.tile(ctx)),
          file=sys.stderr, flush=True)
    return fetch_stages.hist_delta_mean(ctx, "ttft_dev_wait_ms")
