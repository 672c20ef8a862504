"""Step programs: % of the capture's device busy time in the always-on shared
experts beside the routed ones, the `moe_shared` scope over all programs.
The configuration lists the scope (`scopes`), so `dev_ffn_share` beside it
holds the routed experts (and a dense layer's MLP) alone.  A capture without
the scope (the parent, a configuration that does not list it) has nothing to
read: None, not 0."""
import scope_reduce

SCOPE = "moe_shared"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
