"""Step programs: p50 of ttft_breakdown_ms.prefill over the window."""
import readers


def read(ctx):
    return readers.hist_delta_quantile(ctx, "ttft_prefill_ms", 0.5)
