"""Step programs: % of the capture's device busy time in the learned key
selection, the `attn_index` scope over all programs: the indexer's
projections, its scores over each lane's live keys (the read of the indexer's
own pool rows included) and the exact top-k.  The configuration lists the
scope (`scopes`).  A capture without the scope (the parent, a configuration
without an indexer) has nothing to read: None, not 0."""
import scope_reduce

SCOPE = "attn_index"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
