"""Step programs: % of the capture's device busy time in the attention proper
of SLIDING-WINDOW layers, the `attn_window` scope (inside `attn_core`) over
all programs: the windowed Pallas decode and flash-prefill calls.  The
configuration lists the scope (`scopes`), so `dev_attn_share` beside it holds
the global layers alone.  A capture without the scope (the parent, a
configuration that does not list it) has nothing to read: None, not 0."""
import scope_reduce

SCOPE = "attn_window"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
