"""Admission: p90 of ttft_breakdown_ms.queue_wait over the window."""
import readers


def read(ctx):
    return readers.hist_delta_quantile(ctx, "ttft_queue_ms", 0.9)
