"""Step programs: % of the capture's device busy time in the attention proper
of CROSS layers, the `attn_cross` scope (inside `attn_core`) over all
programs: the layers of a hybrid decoder's second half that read the one full
layer's rows and write none, seven more reads of one cache a pass.  The
configuration lists the scope (`scopes`), so `dev_attn_share` beside it holds
the full layer's own read.  A capture without the scope has nothing to read:
None, not 0."""
import scope_reduce

SCOPE = "attn_cross"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
