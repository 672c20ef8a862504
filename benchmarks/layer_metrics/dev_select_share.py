"""Step programs: % of the capture's device busy time in the read of the
rows the indexer kept, the `attn_select` scope (inside `attn_core`, decode of
a layer with an indexer); attention over the rows stays under `attn_core`.
The configuration lists the scope (`scopes`).  A capture without the scope
has nothing to read: None, not 0."""
import scope_reduce

SCOPE = "attn_select"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
