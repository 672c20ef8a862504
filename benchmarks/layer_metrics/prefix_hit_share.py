"""Prefix cache: sum cached_tokens / sum prompt_tokens over the window's
finished requests, from the client's `usage`."""
import e2e


def read(ctx):
    rows = [r for r in ctx["log"] if r["in_window"] and e2e.ok(r)
            and r.get("usage")]
    prompt = sum(r["usage"]["prompt_tokens"] for r in rows)
    if prompt <= 0:
        return None
    return 100.0 * sum(e2e.cached_tokens(r) for r in rows) / prompt
