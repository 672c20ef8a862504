"""Step programs: % of the capture's device busy time in QK-norm, the per-head
RMSNorm of q and k ahead of the rotation (the `qk_norm` scope over all
programs).  The configuration lists the scope (`scopes`), so it is a
component of its own and part of no other share.  A capture without the scope
(the parent, a configuration that does not list it) has nothing to read:
None, not 0."""
import scope_reduce

SCOPE = "qk_norm"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
