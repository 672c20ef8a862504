"""Fetch pipeline: p50 of ttft_breakdown_ms.first_fetch over the window."""
import readers


def read(ctx):
    return readers.hist_delta_quantile(ctx, "ttft_fetch_ms", 0.5)
