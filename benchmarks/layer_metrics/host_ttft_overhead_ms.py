"""HTTP + agent loop + provider: client ttft_p50 minus the engine's own
ttft_ms p50 over the window (submit to first token)."""
import readers


def read(ctx):
    client = ctx["summary"]["ttft_p50_ms"]
    engine = readers.hist_delta_quantile(ctx, "ttft_ms", 0.5)
    if client is None or engine is None:
        return None
    return client - engine
